#!/usr/bin/env python3
"""Readings of a cell's control and planted faults, for setting limits.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]
        [--stand-ins <name> ...]

Not part of a benchmark run. For each seed it builds the inputs of the
cell's first call exactly as a run does, puts a stand-in in the
program's place, and prints each number the run compares, as JSON lines:

* campaign cells: ``control`` -- the reference in bfloat16 (weights,
  activations and updates), the nearest precision below the float32 the
  configuration states; ``half_batch`` -- the reference at the program's
  precision with half of every client batch left out, the mean taken over
  the rest; ``program_precision`` -- the reference at the program's
  default matmul precision, no fault: what precision alone reads;
* equilibrium cells (``--fleets`` of the first batch): ``control`` -- the
  reference solve and certificate in float32, below the float64 the
  configuration states; ``altered`` -- the float64 reference's answers
  with one node's probability moved by 0.01 after its certificate was
  taken.

``--stand-ins`` keeps only the campaign stand-ins named. A state left
unchanged reads 1 on ``param_change`` by its definition and needs no run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def campaign(ctx, seed: int, only: list[str] | None = None) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from bench import inputs
    from bench.reference import resnet as ref

    cfg, tr = ctx.config, ctx.traffic
    model = cfg["model"]
    data = inputs.TemplateNoise(cfg["data"], model)
    val = data.val_batch(cfg["val_size"])

    def init(key):
        return inputs.resnet18_init(model, key)

    b = len(tr["p"])
    seeds = np.random.default_rng([seed, 1]).integers(
        0, 2**32, (4096, b), dtype=np.uint64).astype(np.uint32)[0]
    want_c = ref.Campaign(cfg, data, init)
    stand_ins = {
        "control": ref.Campaign(cfg, data, init, precision="default",
                                dtype=jnp.bfloat16),
        "half_batch": ref.Campaign(cfg, data, init, precision="default",
                                   half_batch=True),
        "program_precision": ref.Campaign(cfg, data, init,
                                          precision="default"),
    }
    if only:
        stand_ins = {k: v for k, v in stand_ins.items() if k in only}
    out = {k: 0.0 for k in stand_ins}
    ledger = 0.0
    for s, p in enumerate(tr["p"]):
        p_row = np.full(cfg["n_clients"], p)
        want = want_c.run(p_row, int(seeds[s]), val)
        for name, camp in stand_ins.items():
            got = camp.run(p_row, int(seeds[s]), val)
            out[name] = max(out[name], ref.change_gap(got["params"], want))
        # One node's participation in round 0 flipped where it is drawn.
        step = cfg["e_participant_j"] - cfg["e_idle_j"]
        ledger = max(ledger, step / want["ledger_j"][0])
    return {"param_change": out, "ledger_rel": {"mask_altered": ledger}}


def equilibrium(ctx, seed: int, fleets: int) -> dict:
    import jax
    import numpy as np

    from bench import inputs
    from bench.drivers.ne_sweep import check_answers
    from bench.reference import ne as ref

    cfg = ctx.config
    key = jax.random.PRNGKey(seed)
    costs, gammas = jax.jit(lambda k: inputs.ne_fleets(
        k, fleets, cfg["n_nodes"], cfg["costs"], cfg["gammas"]))(
            jax.random.fold_in(key, 0))
    costs, gammas = np.asarray(costs), np.asarray(gammas)
    s = cfg["solver"]
    out = {}
    for name, dtype in (("control", np.float32), ("reference", np.float64)):
        c, g = costs.astype(dtype), gammas.astype(dtype)
        table = ref.duration_table(cfg["n_nodes"], cfg["duration"], dtype)
        p, conv, _ = ref.solve(c, g, table, damping=s["damping"],
                               max_iters=s["max_iters"], tol=s["tol"],
                               p0=s["p0"], p_min=cfg["p_min"])
        dev = ref.deviation(c, g, table, p, grid=cfg["verify_grid"],
                            p_min=cfg["p_min"])
        certified = conv & (dev <= cfg["cert_tol"])
        if name == "reference":
            p = p.copy()
            p[:, 0] = np.clip(p[:, 0] + 0.01, 0.0, 1.0)
            name = "altered"
        rows = [(costs, gammas, p.astype(np.float64),
                 dev.astype(np.float64), certified)]
        out[name] = {c["name"]: c["value"]
                     for c in check_answers(cfg, ctx.limits, rows)}
    return out


def main() -> int:
    sys.path[0] = str(ROOT)
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fleets", type=int, default=64)
    ap.add_argument("--stand-ins", nargs="+", default=None)
    args = ap.parse_args()

    from bench import run

    bench = run._load_json(ROOT / "BENCHMARK.json")
    cell, config, traffic, limits = run._cell(bench, args.workload)
    _, devices = run._start_jax(cell["chips"], require_chip=False)
    sys.path.insert(0, str(ROOT / "src"))
    import repro.core  # noqa: F401  (x64 on, as in a run)

    ctx = run.Context(cell, config, traffic, limits, 0, 0.0, False, devices)
    for seed in args.seeds:
        if traffic["driver"] == "campaign":
            reading = campaign(ctx, seed, args.stand_ins)
        else:
            reading = equilibrium(ctx, seed, args.fleets)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": devices[0].device_kind, **reading}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
