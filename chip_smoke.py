#!/usr/bin/env python3
"""Drive the system's main path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: the scenario-mesh path only

Everything runs in this one process, through the library's public entry
points, with random data made from fixed seeds. Each phase prints one line
(name, sizes, seconds, compile seconds and persistent-cache hits, the check
it made) and raises on a failed check, which ends the run with a non-zero
exit. The last line printed is one JSON object naming the device.

One chip:

1. device   -- a TPU, or exit non-zero naming the platform found;
2. game     -- heterogeneous NE solve -> certification -> PoA report and a
               symmetric (gamma, c) grid at the paper's N=50, certificates
               of two benchmark pool batches held to a direct numpy one,
               one Mechanism PoA evaluation, a small coalition partition
               solve;
3. kernels  -- the Pallas FedAvg merge and Poisson-binomial kernels,
               compiled (not interpreted) and held to the jnp references;
4. campaign -- ResNet-18 at its published widths (11,173,962 params) through
               ``model_task`` -> ``run_campaigns``, with the "ref" and the
               "pallas" FedAvg merge;
5. service  -- a ``SweepService`` answering NE, calibration and campaign
               requests, bitwise equal to the direct solves.

Four chips (``--chips 4``): the heterogeneous NE sweep and one campaign
sweep sharded over a ``("data",)`` mesh of the four chips, each compared
with the same call on one device.

The persistent compilation cache is on (``repro.launch.compile_cache``):
``JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache/`` in the checkout,
so a second run shows its hits in the compile columns.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

N_PAPER = 50                  # the paper's fleet of IoT nodes
# ResNet-18 at the published widths (stem 64, stages 64-512, 10 classes)
# with the CIFAR 3x3 stem. The paper's Table I count, 11,181,642, is the
# same network with torchvision's 7x7 stem: 7,680 more stem weights.
RESNET18_PARAMS = 11_173_962
NE_TOL = 1e-4                 # NE certification bound of tests/helpers.py
PARITY = 2e-6                 # pallas vs ref, sharded vs one device
GAME_FLEETS = 256             # heterogeneous fleets per sweep
# Batches of the ne-hetero-n50 benchmark pool (fold_in(PRNGKey(2503), j))
# whose certificates read up to 1.4e-7 off a direct one at the host's
# duration table while the table's fit ran on the chip.
CERT_BATCHES = (2, 6)
CERT_GAP = 1e-8               # the sound readings are ~2e-10
# One vmapped campaign round holds every client's params, gradients and
# activations, ~0.55 GB per (scenario, client) at full width: the paper's
# N=50 needs ~27 GB, over the 16 GB of HBM. N=8 x B=2 compiles to ~9 GB
# of temp (10 GB with the pallas merge); N=10 x B=2 to 12.6 GB.
CAMPAIGN_N = 8
CAMPAIGN_PS = (0.1, 0.7)      # ends of Table II's participation range
CAMPAIGN_ROUNDS = 2
# An SGD step that lowers the validation loss: at 0.05 the full-width model
# ended 2 rounds near 9, against ln 10 = 2.3 for chance.
CAMPAIGN_LR = 0.002
# Coalition fleets as in benchmarks/coalition_sweep.py: its compile grows
# steeply with N and M (N=50, M=4 takes minutes).
COALITION = dict(fleets=16, n=12, m=3, cap=6)


class _CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit is counted in the compile seconds too,
    as the time it took to load)."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.hits, self.misses


def _require(ok, what: str) -> None:
    if not bool(ok):
        raise RuntimeError(f"check failed: {what}")


def _phase(log: _CompileLog, name: str, sizes: str, run) -> None:
    """Run one phase and print its line; ``run`` returns the check text."""
    s0, h0, m0 = log.snapshot()
    t0 = time.perf_counter()
    check = run()
    wall = time.perf_counter() - t0
    s1, h1, m1 = log.snapshot()
    print(f"[{name}] {sizes} | {wall:.1f} s, compile {s1 - s0:.1f} s, "
          f"cache hits {h1 - h0}/{(h1 - h0) + (m1 - m0)} | {check}",
          flush=True)


def _max_abs(a, b) -> float:
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float64)
                                 - jnp.asarray(b, jnp.float64))))


def _tree_max_abs(a, b) -> float:
    return max(_max_abs(x, y) for x, y in zip(jax.tree.leaves(a),
                                              jax.tree.leaves(b)))


def _fleets(batch: int, n: int, seed: int):
    """Heterogeneous (costs, gammas) as in benchmarks/heterogeneous_sweep."""
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.uniform(0.5, 12.0, (batch, n))),
            jnp.asarray(rng.uniform(0.2, 1.0, (batch, n))))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def game_heterogeneous() -> str:
    from repro.core import (poa_report, solve_heterogeneous,
                            theoretical_duration, verify_equilibrium_batched)

    costs, gammas = _fleets(GAME_FLEETS, N_PAPER, seed=0)
    dur = theoretical_duration(N_PAPER)
    solver = dict(damping=0.6, max_iters=300)
    sol = solve_heterogeneous(costs, gammas, dur, **solver)
    dev = verify_equilibrium_batched(costs, gammas, dur, sol.p)
    rep = poa_report(costs, gammas, dur, **solver)
    max_dev = float(jnp.max(dev))
    _require(max_dev <= NE_TOL, f"NE certified: max deviation {max_dev}")
    _require(jnp.array_equal(rep.solution.p, sol.p),
             "poa_report re-solves to the same profiles")
    _require(jnp.all(rep.deviation <= NE_TOL), "poa_report certification")
    poa = np.asarray(rep.poa)
    _require(np.all(np.isfinite(poa)) and poa.min() >= 1.0 - 1e-9,
             f"heterogeneous PoA >= 1, got min {poa.min()}")
    return (f"{int(jnp.sum(sol.converged))}/{GAME_FLEETS} converged, all "
            f"certified (max deviation {max_dev:.2e} <= {NE_TOL:g}); "
            f"PoA in [{poa.min():.4f}, {poa.max():.4f}]")


def game_certificate() -> str:
    from repro.core import (solve_heterogeneous, theoretical_duration,
                            verify_equilibrium_batched)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "tests"))
    from helpers import direct_deviation

    dur = theoretical_duration(N_PAPER, d_inf=35.0, slope=4.0, horizon=500.0)
    with jax.default_device(jax.devices("cpu")[0]):
        host_table = np.asarray(dur.table())
    gap = 0.0
    for j in CERT_BATCHES:
        kc, kg = jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(2503), j))
        costs = jax.random.uniform(kc, (512, N_PAPER), jnp.float64, 0.5, 12.0)
        gammas = jax.random.uniform(kg, (512, N_PAPER), jnp.float64, 0.2, 1.0)
        sol = solve_heterogeneous(costs, gammas, dur)
        dev = verify_equilibrium_batched(costs, gammas, dur, sol.p)
        want = direct_deviation(costs, gammas, host_table, sol.p)
        gap = max(gap, float(np.max(np.abs(np.asarray(dev) - want))))
    _require(gap <= CERT_GAP, f"certificate off the direct one by {gap}")
    return (f"pool batches {list(CERT_BATCHES)}: certificate within "
            f"{gap:.2e} of the direct one at the host's table "
            f"(<= {CERT_GAP:g})")


def game_symmetric() -> str:
    from repro.core import UtilityParams, theoretical_duration
    from repro.core.game import P_MIN
    from repro.core.utility import symmetric_player_utility
    from repro.mechanisms import solve_batched

    dur = theoretical_duration(N_PAPER)
    # 32 scenarios: the service's 32-point calibration grid is then the same
    # program (a cold _solve_batched compile takes about a minute).
    g, c = np.meshgrid(np.linspace(0.0, 2.0, 8), np.linspace(0.05, 1.0, 4))
    gammas, costs = jnp.asarray(g.ravel()), jnp.asarray(c.ravel())
    sol = solve_batched(gammas, costs, dur)
    _require(jnp.all(jnp.any(sol.ne_mask, axis=1)), "an NE per scenario")
    poa = np.asarray(sol.poa)
    _require(np.all(np.isfinite(poa)) and poa.min() >= 1.0 - 1e-6,
             f"symmetric PoA >= 1, got min {poa.min()}")

    # Certify every worst NE: no unilateral deviation on a 256-point grid
    # gains more than NE_TOL (the check of tests/helpers.py, vectorized).
    grid = jnp.linspace(P_MIN, 1.0, 256)

    def deviation(gamma, cost, p_star):
        params = UtilityParams(gamma=gamma, cost=cost, n_nodes=N_PAPER)
        u_eq = symmetric_player_utility(p_star, p_star, params, dur)
        u_dev = jax.vmap(lambda q: symmetric_player_utility(
            q, p_star, params, dur))(grid)
        return jnp.max(u_dev) - u_eq

    dev = jax.jit(jax.vmap(deviation))(gammas, costs, sol.worst_ne)
    max_dev = float(jnp.max(dev))
    _require(max_dev <= NE_TOL, f"symmetric NE certified: {max_dev}")
    return (f"{gammas.size} (gamma, c) scenarios, every worst NE certified "
            f"(max deviation {max_dev:.2e}); PoA in "
            f"[{poa.min():.4f}, {poa.max():.4f}]")


def game_mechanism() -> str:
    from repro.core import UtilityParams, theoretical_duration
    from repro.mechanisms import AoIRewardMechanism, evaluate_mechanism

    base = UtilityParams(gamma=0.0, cost=0.3, n_nodes=N_PAPER)
    rep = evaluate_mechanism(AoIRewardMechanism(gamma_star=1.0), base,
                             theoretical_duration(N_PAPER))
    _require(rep.equilibria, "the induced game has an NE")
    _require(np.all(np.isfinite(rep.ne_costs)), "finite NE social costs")
    _require(np.isfinite(rep.poa) and rep.poa >= 1.0 - 1e-6,
             f"mechanism PoA >= 1, got {rep.poa}")
    return (f"aoi_reward gamma*=1.0 at c=0.3: NE p={rep.ne_p:.4f}, "
            f"PoA {rep.poa:.4f}, IR slack {rep.ir_slack:.2e}")


def game_coalition() -> str:
    from repro.core import (solve_partition, theoretical_duration,
                            verify_partition_batched)

    b, n, m, cap = (COALITION[k] for k in ("fleets", "n", "m", "cap"))
    costs, gammas = _fleets(b, n, seed=1)
    dur = theoretical_duration(n)
    inner = dict(tol=1e-10, max_iters=600)
    sol = solve_partition(costs, gammas, dur, n_coalitions=m, cap=cap,
                          **inner)
    dev = verify_partition_batched(costs, gammas, dur, sol.assign, sol.p,
                                   n_coalitions=m, cap=cap, **inner)
    _require(jnp.all(sol.converged & sol.inner_converged),
             "every partition dynamics converged")
    max_dev = float(jnp.max(dev))
    _require(max_dev <= 1e-6, f"partitions certified: {max_dev}")
    return (f"{b} fleets all stable and certified (max deviation "
            f"{max_dev:.2e} <= 1e-6), switches up to "
            f"{int(jnp.max(sol.switches))}")


def _require_kernel(lowered, what: str) -> None:
    """Refuse a program without a Mosaic kernel in it (an interpret-mode
    fallback lowers to plain XLA ops)."""
    _require("tpu_custom_call" in lowered.as_text(),
             f"{what} holds the compiled Pallas kernel (tpu_custom_call)")


def _compiled_pallas(fn, *args):
    """Lower and compile ``fn``, refusing it unless it holds a kernel."""
    lowered = jax.jit(fn).lower(*args)
    _require_kernel(lowered, "the program")
    return lowered.compile()


def kernels() -> str:
    from repro.kernels import ops

    key = jax.random.PRNGKey(0)
    kg, kc, km, kp = jax.random.split(key, 4)
    g = jax.random.normal(kg, (RESNET18_PARAMS,), jnp.float32)
    c = jax.random.normal(kc, (CAMPAIGN_N, RESNET18_PARAMS), jnp.float32)
    mask = jax.random.bernoulli(km, 0.5, (CAMPAIGN_N,))
    merge = _compiled_pallas(
        lambda g, c, m: ops.fedavg(g, c, m, backend="pallas"), g, c, mask)
    d_merge = _max_abs(merge(g, c, mask),
                       ops.fedavg(g, c, mask, backend="ref"))
    _require(d_merge <= PARITY, f"fedavg_agg vs ref: {d_merge}")

    p = jax.random.uniform(kp, (64, N_PAPER), jnp.float64)
    poibin = _compiled_pallas(lambda p: ops.poibin(p, backend="pallas"), p)
    pmf, loo = poibin(p)
    pmf_ref, loo_ref = ops.poibin(p, backend="ref")
    d_pmf, d_loo = _max_abs(pmf, pmf_ref), _max_abs(loo, loo_ref)
    _require(max(d_pmf, d_loo) <= PARITY,
             f"poibin_dft vs ref: pmf {d_pmf}, loo {d_loo}")
    return (f"both compiled (tpu_custom_call); fedavg_agg max |pallas-ref| "
            f"{d_merge:.2e}, poibin_dft pmf {d_pmf:.2e} loo {d_loo:.2e} "
            f"(<= {PARITY:g})")


def campaign() -> str:
    from repro.configs import ARCHITECTURES
    from repro.federated.campaign import build_campaign, run_campaigns
    from repro.federated.server import fedavg_merge
    from repro.federated.simulation import FLConfig
    from repro.federated.tasks import model_task
    from repro.optim import sgd

    task = model_task(ARCHITECTURES["resnet18-cifar"], val_size=64)
    n_params = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(task.init_params, jax.random.PRNGKey(0))))
    _require(n_params == RESNET18_PARAMS,
             f"ResNet-18 at published width: {n_params} params")
    fl = FLConfig(n_clients=CAMPAIGN_N, local_steps=1, batch_per_client=32,
                  max_rounds=CAMPAIGN_ROUNDS, seed=0)
    opt = sgd(CAMPAIGN_LR)
    ps = jnp.asarray(CAMPAIGN_PS, jnp.float64)
    b = ps.size
    # The engine's arguments as run_campaigns passes them: (B, N) p, seeds,
    # per-scenario joule rates.
    args = (jnp.broadcast_to(ps[:, None], (b, CAMPAIGN_N)),
            jnp.zeros((b,), jnp.uint32), jnp.zeros((b,)), jnp.zeros((b,)))
    val_loss = jax.jit(jax.vmap(task.loss_fn, in_axes=(0, None)))
    # Every scenario starts from the init of seed 0 (the engine's
    # init_params(fold_in(PRNGKey(seed), 1))).
    init = jax.jit(task.init_params)(jax.random.fold_in(
        jax.random.PRNGKey(0), 1))
    loss0 = np.asarray(val_loss(jax.tree.map(
        lambda x: jnp.stack([x] * b), init), task.val_batch))

    def run(backend):
        t0 = time.perf_counter()
        lowered = build_campaign(fl, *task.campaign_args(), opt,
                                 backend=backend).lower(*args)
        if backend == "pallas":
            _require_kernel(lowered, "the pallas-merge engine")
        engine = lowered.compile()
        compile_s = time.perf_counter() - t0
        mem = engine.memory_analysis()
        res = run_campaigns(fl, *task.campaign_args(), opt, ps, engine=engine)
        jax.block_until_ready(res.params)
        t0 = time.perf_counter()
        res = run_campaigns(fl, *task.campaign_args(), opt, ps, engine=engine)
        jax.block_until_ready(res.params)
        step = (time.perf_counter() - t0) / CAMPAIGN_ROUNDS
        loss = np.asarray(val_loss(res.params, task.val_batch))
        _require(np.all(np.isfinite(loss)), f"{backend}: finite losses")
        _require(all(bool(jnp.all(jnp.isfinite(x)))
                     for x in jax.tree.leaves(res.params)),
                 f"{backend}: finite merged params")
        return res, (f"{backend}: compile {compile_s:.1f} s, compiled temp "
                     f"{mem.temp_size_in_bytes} + arguments "
                     f"{mem.argument_size_in_bytes} + outputs "
                     f"{mem.output_size_in_bytes} bytes, round {step:.3f} s, "
                     f"val loss {loss.tolist()}")

    (ref, ref_line), (pal, pal_line) = run("ref"), run("pallas")
    _require(jnp.array_equal(ref.k_history, pal.k_history)
             and jnp.array_equal(ref.ledger.per_node_j, pal.ledger.per_node_j),
             "masks and ledgers independent of the merge backend")
    # The two merges on identical inputs, vmapped over the B scenarios as
    # the engine runs them: scenario i merges N clients, the four trained
    # models twice over (its own two first), into its "ref" params, under
    # its own mask. N as in the engine: at N=4 the kernel's client buffer
    # takes ~75 s to compile for a v5e, at N=8 2 s.
    order = [[0, 1], [1, 0]]
    glob = ref.params
    clients = jax.tree.map(
        lambda r, p: jnp.stack([jnp.stack([r[i], p[i], r[j], p[j]] * 2)
                                for i, j in order]),
        ref.params, pal.params)
    mask = jnp.asarray([[1, 0, 1, 1, 0, 1, 1, 0],
                        [1, 1, 0, 1, 1, 0, 0, 1]], bool)
    merges = {be: jax.vmap(lambda g, c, m, be=be: fedavg_merge(
        g, c, m, backend=be)) for be in ("ref", "pallas")}
    merged = _compiled_pallas(merges["pallas"], glob, clients, mask)(
        glob, clients, mask)
    d_merge = _tree_max_abs(merged,
                            jax.jit(merges["ref"])(glob, clients, mask))
    _require(d_merge <= PARITY, f"vmapped fedavg_merge pallas vs ref: "
             f"{d_merge}")
    d_params = _tree_max_abs(ref.params, pal.params)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", "not reported")
    return (f"{n_params} params; participants/round "
            f"{np.asarray(ref.k_history).tolist()}; val loss at init "
            f"{loss0.tolist()}; {ref_line}; {pal_line}; fedavg_merge "
            f"vmapped over B={b} on the trained models max |pallas-ref| "
            f"{d_merge:.2e} (<= {PARITY:g}); campaign params after "
            f"{CAMPAIGN_ROUNDS} rounds max |pallas-ref| {d_params:.2e} "
            f"(reported, not bounded); allocator peak_bytes_in_use {peak}")


def service() -> str:
    from repro.core import (solve_heterogeneous, theoretical_duration,
                            verify_equilibrium_batched)
    from repro.core.energy import J_PER_WH
    from repro.federated.campaign import run_campaigns
    from repro.federated.simulation import FLConfig
    from repro.federated.tasks import synthetic_mlp_task
    from repro.mechanisms import solve_batched
    from repro.optim import sgd
    from repro.serve import SCHEMA, SweepService

    # Row counts fill their batch rungs (4 NE rows, a 32-point gamma grid,
    # one campaign), so each served program is the direct one.
    costs, gammas = _fleets(4, N_PAPER, seed=2)
    cal = dict(n_nodes=N_PAPER, cost=0.3, grid=32, gamma_max=5.0)
    camp = dict(p=0.5, n_clients=5, rounds=3, seed=1)
    payloads = (
        [{"schema": SCHEMA, "kind": "ne_solve", "costs": c.tolist(),
          "gammas": g.tolist()}
         for c, g in zip(np.asarray(costs), np.asarray(gammas))]
        + [{"schema": SCHEMA, "kind": "calibrate", **cal},
           {"schema": SCHEMA, "kind": "campaign", **camp}])
    task, opt = synthetic_mlp_task(), sgd(0.15)
    with SweepService(max_batch=64, task=task, opt=opt) as svc:
        resps = svc.serve(payloads)
    _require(all(r.ok for r in resps),
             f"every request answered: {[r.error for r in resps if not r.ok]}")
    ne, (cal_r,), (camp_r,) = resps[:4], resps[4:5], resps[5:]

    dur = theoretical_duration(N_PAPER, d_inf=35.0, slope=8.0, horizon=500.0)
    sol = solve_heterogeneous(costs, gammas, dur)
    dev = verify_equilibrium_batched(costs, gammas, dur, sol.p)
    for i, r in enumerate(ne):
        _require(np.array_equal(np.asarray(r.result["p"]),
                                np.asarray(sol.p[i]))
                 and r.result["iters"] == int(sol.iters[i])
                 and r.result["deviation"] == float(dev[i]),
                 f"ne_solve row {i} bitwise equal to the direct solve")

    grid = np.linspace(0.0, cal["gamma_max"], cal["grid"])
    direct = solve_batched(jnp.asarray(grid),
                           jnp.full(cal["grid"], cal["cost"]), dur)
    poa = np.asarray(direct.poa)
    ok = np.isfinite(poa) & (poa <= 1.05)
    first = int(np.argmax(ok)) if ok.any() else int(np.argmin(poa))
    _require(cal_r.result["gamma_star"] == float(grid[first])
             and cal_r.result["poa"] == float(poa[first])
             and cal_r.result["p_ne"] == float(direct.worst_ne[first]),
             "calibrate bitwise equal to the direct solve")

    fl = FLConfig(n_clients=camp["n_clients"], local_steps=1,
                  batch_per_client=8, max_rounds=camp["rounds"],
                  target_acc=0.73, consecutive=3)
    res = run_campaigns(fl, *task.campaign_args(), opt,
                        jnp.full((1, camp["n_clients"]), camp["p"],
                                 jnp.float64),
                        seeds=jnp.asarray([camp["seed"]], jnp.uint32))
    # The service sums the ledger on the host, as done here; on the chip
    # res.energy_wh is an emulated-f64 device sum and may differ in the
    # last bits.
    direct = {"energy_wh": float(np.asarray(res.ledger.per_node_j)[0].sum()
                                 / J_PER_WH),
              "final_acc": float(res.acc_history[0, -1]),
              "mean_aoi": float(res.mean_aoi[0])}
    served = {k: camp_r.result[k] for k in direct}
    _require(served == direct,
             f"campaign bitwise equal to run_campaigns: {served} vs {direct}")
    buckets = ", ".join(sorted({r.bucket for r in resps}))
    return (f"{len(resps)} requests answered ({buckets}), all bitwise "
            f"equal to the direct solves; gamma*="
            f"{cal_r.result['gamma_star']:.4f}")


def _spread(arr, n_dev: int) -> bool:
    """Whether ``arr``'s leading axis is split over ``n_dev`` devices."""
    shards = arr.addressable_shards
    return (len({s.device for s in shards}) == n_dev
            and all(s.data.shape[0] == arr.shape[0] // n_dev
                    for s in shards))


def _mesh4():
    from jax.sharding import Mesh

    n_dev = len(jax.devices())
    _require(n_dev == 4, f"four chips, found {n_dev}")
    return Mesh(np.array(jax.devices()), ("data",))


def four_chips_ne() -> str:
    from repro.core import solve_heterogeneous, theoretical_duration

    mesh = _mesh4()
    costs, gammas = _fleets(GAME_FLEETS, N_PAPER, seed=0)
    dur = theoretical_duration(N_PAPER)
    one = solve_heterogeneous(costs, gammas, dur)
    many = solve_heterogeneous(costs, gammas, dur, mesh=mesh)
    _require(_spread(many.p, mesh.size), "NE profiles spread over all chips")
    _require(np.array_equal(np.asarray(one.p), np.asarray(many.p))
             and np.array_equal(np.asarray(one.iters),
                                np.asarray(many.iters)),
             "sharded NE profiles bitwise equal to one device")
    return (f"{GAME_FLEETS // mesh.size} fleets on each of {mesh.size} "
            f"chips; profiles and iterations bitwise equal to one device")


def four_chips_campaign() -> str:
    from repro.federated.campaign import run_campaigns
    from repro.federated.simulation import FLConfig
    from repro.federated.tasks import synthetic_mlp_task
    from repro.optim import sgd

    mesh = _mesh4()
    # The campaign of the sharding contract's tests
    # (tests/test_sharded_campaign.py), B=8 over the four chips.
    task, opt = synthetic_mlp_task(), sgd(0.15)
    fl = FLConfig(n_clients=5, local_steps=1, batch_per_client=8,
                  max_rounds=6, target_acc=0.73, seed=3)
    ps = jnp.linspace(0.25, 0.85, 8).astype(jnp.float32)
    c_one = run_campaigns(fl, *task.campaign_args(), opt, ps)
    c_many = run_campaigns(fl, *task.campaign_args(), opt, ps, mesh=mesh)
    _require(_spread(c_many.ledger.per_node_j, mesh.size),
             "campaign ledgers spread over all chips")
    _require(np.array_equal(np.asarray(c_one.ledger.per_node_j),
                            np.asarray(c_many.ledger.per_node_j))
             and np.array_equal(np.asarray(c_one.k_history),
                                np.asarray(c_many.k_history)),
             "sharded ledgers and masks bitwise equal to one device")
    d_params = _tree_max_abs(c_one.params, c_many.params)
    _require(d_params <= PARITY, f"sharded params: {d_params}")
    return (f"2 campaigns on each of {mesh.size} chips; ledgers and masks "
            f"bitwise equal to one device; max |params| {d_params:.2e} "
            f"<= {PARITY:g}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the scenario-mesh path on four chips")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import repro.core  # noqa: F401  (x64 on, as for every user)

    log = _CompileLog()
    count = len(jax.devices())
    print(f"[device] {dev.platform} {dev.device_kind} x{count} | jax "
          f"{jax.__version__}, compile cache {cache_dir}", flush=True)
    if args.chips == 4:
        _phase(log, "four_chips.ne", f"B={GAME_FLEETS} N={N_PAPER}",
               four_chips_ne)
        _phase(log, "four_chips.campaign", "synthetic MLP B=8 N=5 rounds=6",
               four_chips_campaign)
    else:
        _require(count >= 1, "a device")
        _phase(log, "game.heterogeneous",
               f"B={GAME_FLEETS} N={N_PAPER}", game_heterogeneous)
        _phase(log, "game.certificate",
               f"B=512 x {len(CERT_BATCHES)} N={N_PAPER}", game_certificate)
        _phase(log, "game.symmetric", f"8x4 (gamma, c) grid N={N_PAPER}",
               game_symmetric)
        _phase(log, "game.mechanism", f"N={N_PAPER}", game_mechanism)
        _phase(log, "game.coalition",
               "B={fleets} N={n} M={m} cap={cap} (cut from N=50: compile "
               "time)".format(**COALITION), game_coalition)
        _phase(log, "kernels",
               f"fedavg_agg P={RESNET18_PARAMS} N={CAMPAIGN_N}; "
               f"poibin_dft B=64 N={N_PAPER}", kernels)
        _phase(log, "campaign",
               f"resnet18-cifar N={CAMPAIGN_N} (paper: 50; one vmapped "
               f"round at N=50 needs ~27 GB > 16 GB HBM) "
               f"B={len(CAMPAIGN_PS)} p={list(CAMPAIGN_PS)} "
               f"rounds={CAMPAIGN_ROUNDS} local_steps=1 batch_per_client=32",
               campaign)
        _phase(log, "service", "4 ne_solve N=50, calibrate N=50, campaign",
               service)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
