"""Closed-loop equilibrium sweep: one caller solves and certifies batches
of heterogeneous fleets, one batch after another.

Traffic keys: ``batch`` (fleets per call, over all chips; with more than
one chip the batch is sharded over a ``("data",)`` mesh through the
library's ``mesh=``), ``pool`` (``batches`` batches of fleets drawn on the
device from the pool's ``seed`` in set-up: every run sweeps the same
fleets, in an order drawn from ``--seed``, cycling), ``check_batches``
batches drawn from the seed among the first ``check_within`` that
completed are compared with the reference.

A call's time is set by its slowest fleet, and a few fleets take ten
times the sweeps of the median one: fleets drawn afresh for each run made
the work itself differ from seed to seed (fleets_per_s spread 12.5%
between seeds, under 1% between two runs of one seed), hence the pool.

A fleet counts when the solver converged and its own certificate (the
largest gain of a unilateral deviation) is within ``cert_tol``; any other
fleet is ``failed``.
"""
from __future__ import annotations

import functools
import sys
import time

import jax
import numpy as np

from bench import inputs
from bench.drivers import check, sample
from bench.reference import ne as ref


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.tr = ctx.traffic

    def setup(self):
        from repro.core import (solve_heterogeneous, theoretical_duration,
                                verify_equilibrium_batched)

        cfg, ctx = self.cfg, self.ctx
        d = cfg["duration"]
        self.dur = theoretical_duration(cfg["n_nodes"], d_inf=d["d_inf"],
                                        slope=d["slope"],
                                        horizon=d["horizon"])
        self.mesh = None
        if ctx.chips > 1:
            from jax.sharding import Mesh
            self.mesh = Mesh(np.array(ctx.devices), ("data",))
        s = cfg["solver"]
        self.solve = functools.partial(
            solve_heterogeneous, damping=s["damping"],
            max_iters=s["max_iters"], tol=s["tol"], mesh=self.mesh)
        self.verify = functools.partial(
            verify_equilibrium_batched, grid=cfg["verify_grid"],
            mesh=self.mesh)
        fleets = jax.jit(functools.partial(
            inputs.ne_fleets, batch=self.tr["batch"], n=cfg["n_nodes"],
            costs=cfg["costs"], gammas=cfg["gammas"]),
            out_shardings=jax.sharding.SingleDeviceSharding(ctx.devices[0]))
        pool = self.tr["pool"]
        key = jax.random.PRNGKey(pool["seed"])
        self.pool = [fleets(jax.random.fold_in(key, j))
                     for j in range(pool["batches"])]
        self.order = np.random.default_rng([ctx.seed, 4]).permutation(
            pool["batches"])
        self._call(self.pool[self.order[-1]])

    def _call(self, fleets):
        costs, gammas = fleets
        with jax.profiler.TraceAnnotation("bench.call"):
            sol = self.solve(costs, gammas, self.dur)
            dev = self.verify(costs, gammas, self.dur, sol.p)
            conv, iters, dev_h = (np.asarray(sol.converged),
                                  np.asarray(sol.iters), np.asarray(dev))
        ok = conv & (dev_h <= self.cfg["cert_tol"])
        return costs, gammas, sol.p, dev_h, ok, iters

    def scope_maps(self) -> dict:
        return {}

    def window(self, seconds: float) -> dict:
        kept, useful, spans = {}, [], []
        calls = certified = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            t_call = time.perf_counter()
            out = self._call(self.pool[self.order[calls % len(self.order)]])
            spans.append(time.perf_counter() - t_call)
            certified += int(out[4].sum())
            iters = out[5]
            useful.append(float(iters.mean() / max(iters.max(), 1)))
            if calls < self.tr["check_within"]:
                kept[calls] = out
            calls += 1
        elapsed = time.perf_counter() - t0
        med = float(np.median(spans))
        slow = [(i, round(s, 3)) for i, s in enumerate(spans) if s > 1.5 * med]
        print(f"window: {calls} calls, per call {min(spans):.4f} / {med:.4f} "
              f"/ {max(spans):.4f} s (min / median / max); calls over 1.5x "
              f"the median (index, s): {slow}", file=sys.stderr)
        fleets = calls * self.tr["batch"]
        return {"calls": calls, "elapsed_s": elapsed, "kept": kept,
                "certified": certified, "lane_useful": useful,
                "attempted": fleets, "failed": fleets - certified}

    def e2e(self, c: dict) -> dict:
        return {"fleets_per_s": c["certified"] / c["elapsed_s"]}

    def release(self):
        self.solve = self.verify = None

    def check(self, c: dict) -> list[dict]:
        pick = sample(self.ctx.seed, c["kept"], self.tr["check_batches"])
        rows = [(np.asarray(k[0]), np.asarray(k[1]), np.asarray(k[2]), k[3],
                 k[4]) for k in (c["kept"][i] for i in pick)]
        return check_answers(self.cfg, self.ctx.limits, rows)


def check_answers(cfg: dict, limits: dict, rows) -> list[dict]:
    """Judge swept answers by what they say.

    ``rows`` holds ``(costs, gammas, p, deviation, certified)`` batches.
    ``cert_gap``: the largest gap between the deviation an answer reports
    and the reference's deviation at the answer's profile. ``deviation``:
    the reference's largest deviation over the answers reported as
    certified, held to the configuration's certification tolerance.
    """
    table = ref.duration_table(cfg["n_nodes"], cfg["duration"])
    gap = worst = 0.0
    count = 0
    for costs, gammas, p, dev, certified in rows:
        want = ref.deviation(costs, gammas, table, p,
                             grid=cfg["verify_grid"], p_min=cfg["p_min"])
        gap = max(gap, float(np.max(np.abs(dev - want))))
        if certified.any():
            worst = max(worst, float(want[certified].max()))
        count += len(p)
    if not count:
        gap = worst = float("nan")
    return [check("cert_gap", gap, limits["cert_gap"]["limit"]),
            check("deviation", worst, cfg["cert_tol"])]
