"""FedAvg campaigns back to back: ``run_campaigns`` through one AOT engine.

Traffic keys: ``p`` (one participation probability per scenario, the
same for every client), ``merge`` (the engine's FedAvg merge backend),
``check_calls`` calls drawn from the seed among the first
``check_within`` that completed are compared with the reference.

Every call runs B whole campaigns of ``max_rounds`` rounds with fresh
scenario seeds drawn from ``--seed``: those seeds set each scenario's
initial weights and participation masks.
"""
from __future__ import annotations

import dataclasses
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import inputs
from bench.drivers import check, sample
from bench.reference import resnet as ref


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.tr = ctx.traffic

    # -- set-up ----------------------------------------------------------------

    def setup(self):
        from repro.configs import ARCHITECTURES
        from repro.federated.campaign import build_campaign
        from repro.federated.simulation import FLConfig
        from repro.federated.tasks import model_task
        from repro.optim import sgd

        cfg, tr, ctx = self.cfg, self.tr, self.ctx
        model = cfg["model"]
        n, b = cfg["n_clients"], len(tr["p"])
        self.data = inputs.TemplateNoise(cfg["data"], model)
        self.val = self.data.val_batch(cfg["val_size"])
        self.init = functools.partial(inputs.resnet18_init, model)
        task = model_task(ARCHITECTURES[cfg["name"]], val_size=1)
        want = jax.tree.structure(jax.eval_shape(task.init_params,
                                                 jax.random.PRNGKey(0)))
        have = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        if jax.tree.structure(have) != want:
            raise RuntimeError("benchmark weights do not fit the program's "
                               "ResNet-18 parameter tree")
        n_params = sum(x.size for x in jax.tree.leaves(have))
        if n_params != model["param_count"]:
            raise RuntimeError(f"{n_params} params, configuration says "
                               f"{model['param_count']}")
        self.task = dataclasses.replace(
            task, init_params=self.init, client_data=self.data.client_data,
            val_batch=self.val)
        self.fl = FLConfig(n_clients=n, local_steps=cfg["local_steps"],
                           batch_per_client=cfg["batch_per_client"],
                           max_rounds=cfg["max_rounds"],
                           target_acc=cfg["target_acc"],
                           consecutive=cfg["consecutive"])
        self.opt = sgd(cfg["lr"])
        dev = ctx.devices[0]
        self.p = jax.device_put(jnp.broadcast_to(
            jnp.asarray(tr["p"], jnp.float64)[:, None], (b, n)), dev)
        self.rates = (jax.device_put(jnp.full((b,), cfg["e_participant_j"],
                                              jnp.float64), dev),
                      jax.device_put(jnp.full((b,), cfg["e_idle_j"],
                                              jnp.float64), dev))
        seeds = jax.ShapeDtypeStruct((b,), jnp.uint32)
        t0 = time.perf_counter()
        lowered = build_campaign(
            self.fl, *self.task.campaign_args(), self.opt,
            backend=tr["merge"]).lower(self.p, seeds, *self.rates)
        t1 = time.perf_counter()
        self.engine = lowered.compile()
        t2 = time.perf_counter()
        # Scenario seeds: call i takes row i; the warm-up takes its own.
        rng = np.random.default_rng([ctx.seed, 1])
        self.seeds = rng.integers(0, 2**32, (4096, b), dtype=np.uint64
                                  ).astype(np.uint32)
        warm = np.random.default_rng([ctx.seed, 2]).integers(
            0, 2**32, b, dtype=np.uint64).astype(np.uint32)
        self._call(warm)
        mem = self.engine.memory_analysis()
        print(f"campaign engine: trace and lower {t1 - t0:.1f} s, compile "
              f"or load {t2 - t1:.1f} s, first call "
              f"{time.perf_counter() - t2:.1f} s; compiled temp "
              f"{getattr(mem, 'temp_size_in_bytes', None)} B, arguments "
              f"{getattr(mem, 'argument_size_in_bytes', None)} B",
              file=sys.stderr)

    def _call(self, seeds):
        from repro.federated.campaign import run_campaigns

        res = run_campaigns(self.fl, *self.task.campaign_args(), self.opt,
                            self.p, energy_rates_j=self.rates,
                            seeds=jax.device_put(seeds, self.ctx.devices[0]),
                            engine=self.engine)
        jax.block_until_ready(res.params)
        return res

    def scope_maps(self) -> dict:
        from bench.trace import op_names

        return {"jit__engine": op_names(self.engine.as_text())}

    # -- timed window --------------------------------------------------------

    def window(self, seconds: float) -> dict:
        kept = {}
        calls, delivered = 0, 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with jax.profiler.TraceAnnotation("bench.call"):
                res = self._call(self.seeds[calls])
            ks = np.asarray(res.k_history)
            delivered += int(ks.sum())
            if calls < self.tr["check_within"]:
                kept[calls] = res
            calls += 1
        elapsed = time.perf_counter() - t0
        b, n = self.p.shape
        return {"calls": calls, "elapsed_s": elapsed, "kept": kept,
                "client_rounds": calls * b * n * self.fl.max_rounds,
                "delivered_steps": delivered * self.fl.local_steps,
                "attempted": calls * b, "failed": 0}

    def e2e(self, c: dict) -> dict:
        return {"client_rounds_per_s": c["client_rounds"] / c["elapsed_s"]}

    def release(self):
        self.engine = None

    # -- correctness ---------------------------------------------------------

    def check(self, c: dict) -> list[dict]:
        lim = self.ctx.limits
        camp = ref.Campaign(self.cfg, self.data, self.init)
        masks = ledger = change = 0.0
        p = np.asarray(self.p)
        for i in sample(self.ctx.seed, c["kept"], self.tr["check_calls"]):
            res = c["kept"][i]
            for s in range(p.shape[0]):
                want = camp.run(p[s], int(self.seeds[i][s]), self.val)
                got_ks = np.asarray(res.k_history[s])
                masks += float(np.sum(got_ks != want["ks"]))
                got_j = np.asarray(res.ledger.per_node_j[s], np.float64)
                ledger = max(ledger, float(np.max(
                    np.abs(got_j - want["ledger_j"]) / want["ledger_j"])))
                change = max(change, ref.change_gap(
                    jax.tree.map(lambda x: x[s], res.params), want))
        if not c["kept"]:
            change = masks = ledger = float("nan")
        return [check("param_change", change, lim["param_change"]["limit"]),
                check("mask_mismatch", masks, lim["mask_mismatch"]["limit"]),
                check("ledger_rel", ledger, lim["ledger_rel"]["limit"])]
