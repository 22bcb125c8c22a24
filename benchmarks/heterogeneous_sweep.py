"""≥500-scenario heterogeneous-NE sweep: batched engine vs. scalar loop.

The heterogeneous game is where population-scale incentive questions live
(free-rider stratification, heterogeneous PoA, who a uniform reward actually
moves) — and where the seed solver was hopeless: Python-loop Gauss-Seidel
with a full DFT pmf recompute per node per iteration takes seconds for a
*single* N=50 equilibrium. This benchmark times the two ways to run a
B-scenario (costs, gammas) sweep at N=50:

* ``scalar`` — loop ``best_response_dynamics_reference`` (the seed eager
  Gauss-Seidel) over scenarios. A ``--sample`` subset is timed and the total
  extrapolated (the full loop takes hours); pass ``--full-scalar`` for an
  exact number.
* ``batched`` — ``repro.core.asymmetric_batched.solve_heterogeneous``: the
  same damped Gauss-Seidel semantics as one batched jitted XLA program
  (an O(N) leave-one-out step instead of per-node recomputes).

Every batched NE is certified by the jitted ``verify_equilibrium_batched``
(max profitable unilateral deviation ≤ 1e-4) before the speedup is reported.
Emits ``name,us_per_call,derived`` CSV rows like the other benchmarks plus a
final ``speedup`` row; the acceptance bar is ≥ 100×.

Run:  PYTHONPATH=src:. python benchmarks/heterogeneous_sweep.py
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.asymmetric import (HeterogeneousGame,
                                   best_response_dynamics_reference)
from repro.core.asymmetric_batched import (poa_report, solve_heterogeneous,
                                           verify_equilibrium_batched)
from repro.core.duration import theoretical_duration
from benchmarks.common import header, record

N_NODES = 50
DAMPING = 0.6
MAX_ITERS = 300


def build_scenarios(batch: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    costs = jnp.asarray(rng.uniform(0.5, 12.0, (batch, N_NODES)))
    gammas = jnp.asarray(rng.uniform(0.2, 1.0, (batch, N_NODES)))
    return costs, gammas, theoretical_duration(N_NODES)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=500,
                    help="scenarios in the sweep (acceptance bar: >= 500)")
    ap.add_argument("--sample", type=int, default=3,
                    help="scalar scenarios to time (extrapolated to all)")
    ap.add_argument("--full-scalar", action="store_true",
                    help="loop the scalar solver over every scenario")
    args = ap.parse_args(argv)

    costs, gammas, dur = build_scenarios(args.batch)
    header()

    # -- batched: warm-up compile, then time one sweep + certification -------
    sol = solve_heterogeneous(costs, gammas, dur, damping=DAMPING,
                              max_iters=MAX_ITERS)
    jax.block_until_ready(sol.p)
    t0 = time.perf_counter()
    sol = solve_heterogeneous(costs, gammas, dur, damping=DAMPING,
                              max_iters=MAX_ITERS)
    jax.block_until_ready(sol.p)
    t_batched = time.perf_counter() - t0
    n_conv = int(jnp.sum(sol.converged))
    record("heterogeneous_sweep.batched_total", t_batched * 1e6,
           f"{args.batch} scenarios N={N_NODES}; {n_conv} converged")

    # certification (also jitted; timed separately so the solve number is
    # comparable to the scalar loop, which certifies nothing)
    dev = verify_equilibrium_batched(costs, gammas, dur, sol.p)
    jax.block_until_ready(dev)
    t0 = time.perf_counter()
    dev = verify_equilibrium_batched(costs, gammas, dur, sol.p)
    jax.block_until_ready(dev)
    t_verify = time.perf_counter() - t0
    max_dev = float(jnp.max(dev))
    record("heterogeneous_sweep.verify_total", t_verify * 1e6,
           f"max profitable deviation {max_dev:.2e} (bar <= 1e-4)")
    assert max_dev <= 1e-4, f"uncertified NE in the batch: {max_dev}"

    # full PoA report (solve + certify + planner + social costs)
    rep = poa_report(costs, gammas, dur, damping=DAMPING,
                     max_iters=MAX_ITERS)
    jax.block_until_ready(rep.poa)
    record("heterogeneous_sweep.poa_report", float("nan"),
           f"heterogeneous PoA in [{float(jnp.min(rep.poa)):.3f}, "
           f"{float(jnp.max(rep.poa)):.3f}]")

    # -- scalar loop (seed implementation) -----------------------------------
    rng = np.random.default_rng(1)
    total = args.batch
    if args.full_scalar:
        idx = np.arange(total)
    else:
        idx = rng.choice(total, size=min(args.sample, total), replace=False)
    t0 = time.perf_counter()
    for i in idx:
        game = HeterogeneousGame(costs=costs[i], gammas=gammas[i], dur=dur)
        best_response_dynamics_reference(game, damping=DAMPING,
                                         max_iters=MAX_ITERS)
    t_scalar_sample = time.perf_counter() - t0
    t_scalar = t_scalar_sample * (total / len(idx))
    tag = "measured" if args.full_scalar else f"extrapolated from {len(idx)}"
    record("heterogeneous_sweep.scalar_total", t_scalar * 1e6,
           f"{total} scenarios ({tag})")

    speedup = t_scalar / t_batched
    record("heterogeneous_sweep.speedup", speedup,
           f"target >= 100x; batched {t_batched:.2f}s vs scalar {t_scalar:.0f}s")
    print(f"\nbatched sweep: {t_batched:.2f}s for {total} scenarios "
          f"({t_batched / total * 1e3:.2f} ms/scenario), "
          f"certification {t_verify:.2f}s, max deviation {max_dev:.2e}")
    print(f"scalar loop:   {t_scalar:.0f}s ({tag}; "
          f"{t_scalar / total * 1e3:.0f} ms/scenario)")
    print(f"speedup: {speedup:.0f}x")


if __name__ == "__main__":
    main()
