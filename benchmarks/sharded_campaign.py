"""Weak-scaling benchmark for the mesh-sharded campaign/NE engines.

Runs on faked CPU devices (``--xla_force_host_platform_device_count=8``,
set by this module itself when launched as a script): a single process
builds meshes over device subsets (1 → 8) and measures, per device count,

* the **campaign engine** — ``run_campaigns(mesh=...)`` at a fixed
  per-device scenario load (weak scaling: B grows with the mesh);
* the **NE engine** — ``solve_heterogeneous(mesh=...)`` scaled up to a
  ≥10⁵-scenario sweep on the full mesh;
* the **equivalence contract** — on the full mesh, with a batch size that
  does *not* divide the device count: ledgers/masks bitwise vs the
  single-device engine, merged model params within 2e-6.

Per device count the artifact records campaigns-or-scenarios/s, the
per-device rate, and weak-scaling efficiency vs the 1-device run. Faked
CPU devices share the host's cores, so efficiency here validates the
*partitioning harness* (no cross-scenario collectives, no replicated
work), not accelerator speedup — on real multi-chip meshes the same
program shards the same way.

Emits ``BENCH_sharded_campaign.json`` (``repro.obs/v1``); rendered into
the README scaling table by ``tools/obs_report.py --readme``.

Run:  PYTHONPATH=src:. python benchmarks/sharded_campaign.py
"""
from __future__ import annotations

from benchmarks.host_devices import fake_host_devices

fake_host_devices(8)  # must precede the jax import to take effect

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

import repro.core  # noqa: F401  (enables x64)
from repro.core.asymmetric_batched import solve_heterogeneous
from repro.core.duration import paper_duration_model
from repro.federated.campaign import build_campaign, run_campaigns
from repro.federated.simulation import FLConfig
from repro.federated.tasks import synthetic_mlp_task
from repro.obs.export import write_artifact
from repro.optim import sgd
from benchmarks.common import header, record


def _mesh(k: int) -> Mesh:
    return Mesh(np.array(jax.devices()[:k]), ("data",))


def _device_counts() -> list[int]:
    return [k for k in (1, 2, 4, 8) if k <= jax.device_count()]


def _timed(fn) -> float:
    jax.block_until_ready(fn())          # warmup (compile + cache)
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    return time.perf_counter() - t0


def _scaling_row(k: int, scenarios: int, warm_s: float,
                 base_rate: float | None) -> dict:
    rate = scenarios / warm_s
    return {
        "devices": k,
        "scenarios": scenarios,
        "warm_s": round(warm_s, 3),
        "throughput_per_s": round(rate, 1),
        "per_device_per_s": round(rate / k, 1),
        "efficiency": (1.0 if base_rate is None
                       else round(rate / (k * base_rate), 3)),
    }


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--campaigns-per-device", type=int, default=16)
    ap.add_argument("--ne-scenarios", type=int, default=100_000,
                    help="NE sweep size on the full mesh (scaled down "
                         "proportionally for smaller meshes)")
    ap.add_argument("--json", default="BENCH_sharded_campaign.json")
    args = ap.parse_args(argv)

    counts = _device_counts()
    full = counts[-1]
    header()
    print(f"# devices: {jax.device_count()} "
          f"(weak-scaling over {counts})", flush=True)

    # -- campaign engine weak scaling ---------------------------------------
    task = synthetic_mlp_task()
    fl = FLConfig(n_clients=6, local_steps=1, batch_per_client=8,
                  max_rounds=16, target_acc=0.73, seed=1)
    opt = sgd(0.15)
    campaign_rows = []
    base_rate = None
    for k in counts:
        b = args.campaigns_per_device * k
        ps = jnp.asarray(np.linspace(0.15, 0.9, b), jnp.float32)
        warm = _timed(lambda: run_campaigns(
            fl, *task.campaign_args(), opt, ps, mesh=_mesh(k)).energy_wh)
        row = _scaling_row(k, b, warm, base_rate)
        base_rate = base_rate or row["throughput_per_s"]
        campaign_rows.append(row)
        record(f"sharded_campaign.campaigns[{k}dev]", warm * 1e6,
               f"{b} campaigns x {fl.max_rounds} rounds; "
               f"{row['throughput_per_s']:.1f}/s, "
               f"eff {row['efficiency']:.2f}")

    # -- NE engine scaling to >= 1e5 scenarios ------------------------------
    n_nodes = 8
    dur = dataclasses.replace(paper_duration_model(), n_nodes=n_nodes)
    rng = np.random.default_rng(0)
    ne_rows = []
    base_rate = None
    for k in counts:
        b = max(1, args.ne_scenarios * k // full)
        costs = jnp.asarray(rng.uniform(0.3, 3.0, (b, n_nodes)))
        gammas = jnp.asarray(rng.uniform(0.0, 2.0, (b, n_nodes)))
        warm = _timed(lambda: solve_heterogeneous(
            costs, gammas, dur, mesh=_mesh(k)).p)
        row = _scaling_row(k, b, warm, base_rate)
        base_rate = base_rate or row["throughput_per_s"]
        ne_rows.append(row)
        record(f"sharded_campaign.ne_solve[{k}dev]", warm * 1e6,
               f"{b} scenarios N={n_nodes}; "
               f"{row['throughput_per_s']:.0f}/s, "
               f"eff {row['efficiency']:.2f}")

    # -- equivalence: full mesh vs single device, non-divisible B -----------
    b_eq = args.campaigns_per_device * full + 3   # deliberately indivisible
    ps = jnp.asarray(np.linspace(0.2, 0.85, b_eq), jnp.float32)
    ref = run_campaigns(fl, *task.campaign_args(), opt, ps)
    sh = run_campaigns(fl, *task.campaign_args(), opt, ps, mesh=_mesh(full))
    ledger_bitwise = all(
        bool(jnp.array_equal(a, c)) for a, c in
        zip(jax.tree.leaves(ref.ledger), jax.tree.leaves(sh.ledger)))
    masks_bitwise = bool(jnp.array_equal(ref.k_history, sh.k_history))
    assert ledger_bitwise and masks_bitwise, \
        "sharded engine diverged from single-device accounting"

    b_par = args.campaigns_per_device * full
    pmat = jnp.broadcast_to(
        jnp.linspace(0.3, 0.8, b_par, dtype=jnp.float32)[:, None],
        (b_par, fl.n_clients))
    seeds = jnp.full((b_par,), fl.seed, jnp.uint32)
    rates = (jnp.full((b_par,), 1.0), jnp.full((b_par,), 0.1))
    bench_args = (fl, *task.campaign_args(), opt)
    ref_params = build_campaign(*bench_args)(pmat, seeds, *rates)["params"]
    sh_params = build_campaign(*bench_args, mesh=_mesh(full))(
        pmat, seeds, *rates)["params"]
    params_diff = max(
        float(jnp.max(jnp.abs(a - c))) for a, c in
        zip(jax.tree.leaves(ref_params), jax.tree.leaves(sh_params)))
    assert params_diff <= 2e-6, f"params diverged: {params_diff}"
    record("sharded_campaign.equivalence", 0.0,
           f"B={b_eq} on {full} devices: ledger bitwise={ledger_bitwise}, "
           f"masks bitwise={masks_bitwise}, "
           f"params max|diff|={params_diff:.1e} (bar 2e-6)")

    write_artifact(args.json, "sharded_campaign", {
        "devices": jax.device_count(),
        "device_counts": counts,
        "campaign": {
            "n_clients": fl.n_clients,
            "max_rounds": fl.max_rounds,
            "campaigns_per_device": args.campaigns_per_device,
            "scaling": campaign_rows,
        },
        "ne": {
            "n_nodes": n_nodes,
            "scaling": ne_rows,
            "total_scenarios": ne_rows[-1]["scenarios"],
        },
        "equivalence": {
            "scenarios": b_eq,
            "ledger_bitwise": ledger_bitwise,
            "masks_bitwise": masks_bitwise,
            "params_max_abs_diff": params_diff,
            "params_tolerance": 2e-6,
        },
    }, seed=fl.seed, backend="ref")
    print(f"\nNE sweep: {ne_rows[-1]['scenarios']:,} scenarios on "
          f"{counts[-1]} device(s) in {ne_rows[-1]['warm_s']:.1f}s "
          f"({ne_rows[-1]['throughput_per_s']:,.0f}/s) -> {args.json}")


if __name__ == "__main__":
    main()
