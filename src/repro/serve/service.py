"""`SweepService` — the long-running in-process scenario-sweep server.

The design-time twin of a production FL control plane: clients submit
schema-versioned requests (:mod:`repro.serve.schema`), the service queues
them, groups compatible rows, pads each dispatch onto the bucketing
ladder (:mod:`repro.serve.bucketing`), runs the repo's existing jitted
batched engines, and streams per-request responses with latency metadata.

Three properties the test harness pins (``tests/test_serve.py``,
``tests/test_serve_bucketing.py``):

* **Parity** — a request served through a padded bucket returns results
  bitwise-equal to calling the engine directly on the unpadded inputs:
  padding lanes are edge-replicas (:func:`repro.launch.sharding.pad_batch`)
  sliced away before assembly, and each bucket's program is AOT-lowered
  from the *same* jitted callable the direct path runs.
* **Compiled-program caching** — programs are cached per
  :class:`~repro.serve.bucketing.Bucket` (family, N, padded batch,
  statics, backend, mesh). A cache hit re-uses the compiled executable;
  the per-bucket ``compile`` stats in :meth:`SweepService.stats` prove the
  second same-bucket request compiles nothing.
* **Total validation** — every traced shape and static argument derives
  from fields validated at :meth:`SweepService.submit`; malformed payloads
  raise typed :class:`~repro.serve.schema.RequestError` and can never
  crash a trace.

Observability rides :mod:`repro.obs`: pass an
:class:`~repro.obs.EventSink` to stream ``serve.request`` /
``serve.dispatch`` / ``serve.complete`` events, and read
:meth:`SweepService.stats` for cache hit rates, padding overhead,
per-bucket compile/cost accounting and the kernel-dispatch counters.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import repro.core  # noqa: F401  (enables x64 — the engines' dtype contract)
from repro.core.duration import theoretical_duration
from repro.core.energy import J_PER_WH, EnergyParams
from repro.launch.sharding import pad_batch
from repro.obs import EventSink
from repro.obs.export import timing_stats
from repro.obs.trace import _merge_cost
from repro.serve.bucketing import (DEFAULT_MAX_BATCH, Bucket, bucket_for,
                                   group_key, padding_overhead)
from repro.serve.schema import (CalibrateRequest, CampaignRequest,
                                NESolveRequest, Request, RequestError,
                                Response, parse_request)

__all__ = ["SweepService"]


@dataclasses.dataclass
class _Pending:
    rid: int
    request: Request
    t_submit: float
    t_dispatch: float | None = None


@dataclasses.dataclass
class _Program:
    """One AOT-compiled bucket program + its compile accounting."""

    bucket: Bucket
    compiled: Any
    lower_s: float
    compile_s: float
    flops: float
    bytes_accessed: float
    calls: int = 0

    def stats(self) -> dict[str, Any]:
        return {"lower_s": round(self.lower_s, 4),
                "compile_s": round(self.compile_s, 4),
                "flops": self.flops,
                "bytes_accessed": self.bytes_accessed,
                "calls": self.calls}


def _f64(shape: tuple) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, jnp.float64)


class SweepService:
    """Persistent padded/bucketed NE + calibration + campaign server.

    Args:
        backend: kernel backend baked into the campaign merge
            (``None``/``"ref"`` keep the bitwise jnp path, ``"pallas"``
            the fused kernel; see :mod:`repro.kernels.ops`).
        mesh: optional :class:`jax.sharding.Mesh` — NE-solve and campaign
            buckets shard their (padded) batch over the mesh's data axes
            exactly like the offline engines; calibrate buckets always run
            unsharded (their grid rows are cheap). Bucket batch rungs are
            padded up to shard divisibility.
        batch_axis: mesh axis override, as in the offline engines.
        max_batch: top rung of the batch-padding ladder (per dispatch).
        task: the :class:`repro.federated.tasks.FLTask` campaign requests
            train (default: :func:`~repro.federated.tasks.synthetic_mlp_task`).
        opt: the optimizer for campaign local training (default SGD 0.15).
        sink: optional :class:`repro.obs.EventSink` receiving request
            lifecycle events.
    """

    def __init__(self, *, backend: str | None = None, mesh=None,
                 batch_axis=None, max_batch: int = DEFAULT_MAX_BATCH,
                 task=None, opt=None, sink: EventSink | None = None):
        self.backend = backend
        self.mesh = mesh
        self.batch_axis = batch_axis
        self.max_batch = int(max_batch)
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self._task = task
        self._opt = opt
        self.sink = sink

        self._queue: deque[_Pending] = deque()
        self._next_rid = 0
        self._programs: dict[Bucket, _Program] = {}
        self._dur_tables: dict[tuple, jax.Array] = {}
        self._rates = EnergyParams()
        self._engines: dict[tuple, Any] = {}   # un-jitted campaign builders

        # counters
        self.submitted = 0
        self.rejected = 0
        self.completed = 0
        self.dispatches = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.rows_real = 0
        self.rows_padded = 0
        self._by_kind: dict[str, int] = {}
        self._latencies_s: list[float] = []

        if mesh is not None:
            from repro.launch.sharding import (scenario_batch_spec,
                                               spec_axis_size)
            spec = scenario_batch_spec(0, mesh, axis=batch_axis)
            self._shards = spec_axis_size(mesh, spec)
            self._mesh_axes: tuple | None = (self._shards,)
        else:
            self._shards = 1
            self._mesh_axes = None

    # -- public api ----------------------------------------------------------

    def submit(self, payload: Any) -> int:
        """Validate and enqueue one request; returns its server ``rid``.

        Raises:
            RequestError: typed rejection — the request never enters the
                queue and no engine is touched.
        """
        try:
            req = parse_request(payload)
        except RequestError:
            self.rejected += 1
            raise
        rid = self._next_rid
        self._next_rid += 1
        self.submitted += 1
        self._by_kind[req.kind] = self._by_kind.get(req.kind, 0) + 1
        self._queue.append(_Pending(rid=rid, request=req,
                                    t_submit=time.perf_counter()))
        if self.sink is not None:
            self.sink.emit("serve.request", rid=rid, kind=req.kind,
                           n=req.n)
        return rid

    def poll(self) -> list[Response]:
        """Run one scheduling cycle: drain the queue, dispatch every group,
        return the completed responses in dispatch-completion order (which
        interleaves request families and may differ from submit order —
        pinned in ``tests/test_serve.py``)."""
        done: list[Response] = []
        while self._queue:
            pending = list(self._queue)
            self._queue.clear()
            groups: dict[tuple, list[_Pending]] = {}
            for pen in pending:
                groups.setdefault(group_key(pen.request), []).append(pen)
            for key, pens in groups.items():
                done.extend(self._dispatch_group(key[0], pens))
        return done

    def serve(self, payloads: Sequence[Any]) -> list[Response]:
        """Submit a batch of raw payloads and poll to completion.

        Malformed payloads become ``ok=False`` responses (typed error
        bodies) instead of raising, so mixed-quality workloads — the
        closed-loop load generator's — stream through uniformly.
        """
        errors: list[Response] = []
        for payload in payloads:
            try:
                self.submit(payload)
            except RequestError as e:
                rid = self._next_rid
                self._next_rid += 1
                kind = payload.get("kind") if isinstance(payload, dict) \
                    else None
                errors.append(Response(
                    rid=rid, kind=kind if kind in ("ne_solve", "calibrate",
                                                   "campaign") else "unknown",
                    ok=False, error=e.to_dict()))
        return self.poll() + errors

    def stats(self) -> dict[str, Any]:
        """Serving counters + per-bucket compile accounting (JSON-able)."""
        from repro.kernels import ops as kernel_ops

        total = self.cache_hits + self.cache_misses
        out: dict[str, Any] = {
            "requests": {"submitted": self.submitted,
                         "rejected": self.rejected,
                         "completed": self.completed,
                         "by_kind": dict(self._by_kind)},
            "dispatches": self.dispatches,
            "rows": {"real": self.rows_real, "padded": self.rows_padded},
            "padding_overhead": round(
                padding_overhead(self.rows_real, self.rows_padded), 4),
            "cache": {"hits": self.cache_hits, "misses": self.cache_misses,
                      "hit_rate": round(self.cache_hits / total, 4)
                      if total else 0.0,
                      "programs": len(self._programs)},
            "compile": {p.bucket.label: p.stats()
                        for p in self._programs.values()},
            "kernel_dispatch": kernel_ops.dispatch_stats(),
        }
        if self._latencies_s:
            out["latency"] = timing_stats(self._latencies_s)
        return out

    def close(self) -> None:
        if self.sink is not None:
            self.sink.flush()

    def __enter__(self) -> "SweepService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- program cache -------------------------------------------------------

    def _program(self, bucket: Bucket, lower) -> _Program:
        """Fetch-or-compile the bucket's executable; counts hits/misses."""
        prog = self._programs.get(bucket)
        if prog is not None:
            self.cache_hits += 1
            return prog
        self.cache_misses += 1
        t0 = time.perf_counter()
        lowered = lower()
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0
        cost = {}
        try:
            cost = _merge_cost(compiled.cost_analysis())
        except Exception:
            pass
        prog = _Program(bucket=bucket, compiled=compiled,
                        lower_s=t_lower, compile_s=t_compile,
                        flops=cost.get("flops", 0.0),
                        bytes_accessed=cost.get("bytes accessed", 0.0))
        self._programs[bucket] = prog
        if self.sink is not None:
            self.sink.emit("serve.compile", bucket=bucket.label,
                           lower_s=round(t_lower, 4),
                           compile_s=round(t_compile, 4))
        return prog

    def _run(self, prog: _Program, *args) -> Any:
        prog.calls += 1
        return prog.compiled(*args)

    # -- shared helpers ------------------------------------------------------

    def _dur_table(self, spec, n: int) -> jax.Array:
        key = (spec, n)
        tab = self._dur_tables.get(key)
        if tab is None:
            if spec.table is not None:
                tab = jnp.asarray(spec.table, jnp.float64)
            else:
                tab = theoretical_duration(
                    n, d_inf=spec.d_inf, slope=spec.slope,
                    horizon=spec.horizon).table()
            self._dur_tables[key] = tab
        return tab

    def _mesh_sharding(self):
        from jax.sharding import NamedSharding

        from repro.launch.sharding import scenario_batch_spec
        spec = scenario_batch_spec(0, self.mesh, axis=self.batch_axis)
        return NamedSharding(self.mesh, spec)

    def _emit_dispatch(self, bucket: Bucket, rows: int, hit: bool) -> None:
        self.dispatches += 1
        self.rows_real += rows
        self.rows_padded += bucket.batch
        if self.sink is not None:
            self.sink.emit("serve.dispatch", bucket=bucket.label,
                           rows=rows, padded=bucket.batch, cache_hit=hit)

    def _finish(self, pen: _Pending, bucket: Bucket,
                result: dict[str, Any]) -> Response:
        now = time.perf_counter()
        latency_s = now - pen.t_submit
        self._latencies_s.append(latency_s)
        self.completed += 1
        resp = Response(
            rid=pen.rid, kind=pen.request.kind, ok=True, result=result,
            id=pen.request.id, bucket=bucket.label,
            latency_us=latency_s * 1e6,
            queue_us=((pen.t_dispatch or now) - pen.t_submit) * 1e6)
        if self.sink is not None:
            self.sink.emit("serve.complete", rid=pen.rid, kind=resp.kind,
                           bucket=bucket.label,
                           latency_us=round(resp.latency_us, 1))
        return resp

    # -- dispatch ------------------------------------------------------------

    def _dispatch_group(self, family: str, pens: list[_Pending]
                        ) -> list[Response]:
        t_dispatch = time.perf_counter()
        for pen in pens:
            pen.t_dispatch = t_dispatch
        if family == "ne":
            return self._dispatch_ne(pens)
        if family == "sym":
            return self._dispatch_calibrate(pens)
        if family == "campaign":
            return self._dispatch_campaign(pens)
        raise AssertionError(f"unknown family {family!r}")

    # .. heterogeneous NE ....................................................

    def _dispatch_ne(self, pens: list[_Pending]) -> list[Response]:
        req0: NESolveRequest = pens[0].request
        n = req0.n
        damping, max_iters, tol, grid = (float(req0.damping),
                                         int(req0.max_iters),
                                         float(req0.tol),
                                         int(req0.verify_grid))
        out: list[Response] = []
        for start_end in _chunks(len(pens), self.max_batch):
            chunk = pens[start_end[0]:start_end[1]]
            rows = len(chunk)
            bucket = bucket_for(req0, rows, max_batch=self.max_batch,
                                backend=None, mesh_axes=self._mesh_axes)
            costs = jnp.asarray([p.request.costs for p in chunk],
                                jnp.float64)
            gammas = jnp.asarray([p.request.gammas for p in chunk],
                                 jnp.float64)
            d_tab = jnp.stack([self._dur_table(p.request.dur, n)
                               for p in chunk])
            p0 = jnp.full((rows, n), 0.5, jnp.float64)
            b = bucket.batch
            args = tuple(pad_batch(a, rows, b)
                         for a in (costs, gammas, d_tab, p0))

            solve_bucket = dataclasses.replace(
                bucket, family="ne/solve", statics=(damping, max_iters, tol))
            verify_bucket = dataclasses.replace(
                bucket, family="ne/verify", statics=(grid,))
            hit = solve_bucket in self._programs

            shapes = (_f64((b, n)), _f64((b, n)), _f64((b, n + 1)),
                      _f64((b, n)))
            if self.mesh is None:
                from repro.core.asymmetric_batched import (_solve_vmapped,
                                                           _verify_vmapped)
                solve = self._program(solve_bucket, lambda: _solve_vmapped
                                      .lower(*shapes, damping=damping,
                                             max_iters=max_iters, tol=tol))
                verify = self._program(verify_bucket, lambda: _verify_vmapped
                                       .lower(*shapes, grid=grid))
            else:
                import functools

                from repro.core.asymmetric_batched import (_gs_fixed_point,
                                                           _verify_one)
                sharding = self._mesh_sharding()

                def lower_solve():
                    fn = functools.partial(_gs_fixed_point, damping=damping,
                                           max_iters=max_iters, tol=tol)
                    return jax.jit(fn, in_shardings=sharding,
                                   out_shardings=sharding).lower(*shapes)

                def lower_verify():
                    fn = functools.partial(_verify_one, grid=grid)
                    return jax.jit(jax.vmap(fn), in_shardings=sharding,
                                   out_shardings=sharding).lower(*shapes)

                solve = self._program(solve_bucket, lower_solve)
                verify = self._program(verify_bucket, lower_verify)

            self._emit_dispatch(bucket, rows, hit)
            p, conv, iters = self._run(solve, *args)
            dev = self._run(verify, args[0], args[1], args[2], p)
            p, conv = np.asarray(p[:rows]), np.asarray(conv[:rows])
            iters, dev = np.asarray(iters[:rows]), np.asarray(dev[:rows])
            for i, pen in enumerate(chunk):
                out.append(self._finish(pen, bucket, {
                    "p": [float(x) for x in p[i]],
                    "converged": bool(conv[i]),
                    "iters": int(iters[i]),
                    "deviation": float(dev[i]),
                }))
        return out

    # .. symmetric γ* calibration ............................................

    def _dispatch_calibrate(self, pens: list[_Pending]) -> list[Response]:
        req0: CalibrateRequest = pens[0].request
        n = req0.n
        d_tab = self._dur_table(req0.dur, n)
        # flatten: each request expands into its γ-grid rows
        row_gammas: list[np.ndarray] = []
        row_costs: list[np.ndarray] = []
        spans: list[tuple[int, int]] = []
        pos = 0
        for pen in pens:
            r: CalibrateRequest = pen.request
            g = r.gamma0 + np.linspace(0.0, r.gamma_max, r.grid)
            row_gammas.append(g)
            row_costs.append(np.full(r.grid, r.cost))
            spans.append((pos, pos + r.grid))
            pos += r.grid
        gam = np.concatenate(row_gammas)
        cos = np.concatenate(row_costs)

        poas = np.empty(pos)
        worst = np.empty(pos)
        opt_p = np.empty(pos)
        opt_cost = np.empty(pos)
        last_bucket: Bucket | None = None
        for start, end in _chunks(pos, self.max_batch):
            rows = end - start
            bucket = bucket_for(req0, rows, max_batch=self.max_batch)
            last_bucket = bucket
            b = bucket.batch
            gammas = pad_batch(jnp.asarray(gam[start:end], jnp.float64),
                               rows, b)
            costs = pad_batch(jnp.asarray(cos[start:end], jnp.float64),
                              rows, b)
            solve_bucket = dataclasses.replace(bucket, family="sym/solve")
            hit = solve_bucket in self._programs

            from repro.mechanisms.batched import _solve_batched
            prog = self._program(solve_bucket, lambda: _solve_batched.lower(
                _f64((b,)), _f64((b,)), _f64((n + 1,)),
                ne_grid=req0.ne_grid, opt_grid=req0.opt_grid, max_roots=4,
                bisect_iters=60, golden_iters=40))
            self._emit_dispatch(bucket, rows, hit)
            sol = self._run(prog, gammas, costs, d_tab)
            poas[start:end] = np.asarray(sol["poa"][:rows])
            worst[start:end] = np.asarray(sol["worst_ne"][:rows])
            opt_p[start:end] = np.asarray(sol["opt_p"][:rows])
            opt_cost[start:end] = np.asarray(sol["opt_cost"][:rows])

        out = []
        for pen, (start, end) in zip(pens, spans):
            r = pen.request
            g = gam[start:end]
            p_req = poas[start:end]
            ok = np.isfinite(p_req) & (p_req <= r.target_poa)
            if ok.any():
                first = int(np.argmax(ok))
                achieved = True
            else:
                finite = np.where(np.isfinite(p_req), p_req, np.inf)
                first = int(np.argmin(finite))
                achieved = False
            out.append(self._finish(pen, last_bucket, {
                "gamma_star": float(g[first]),
                "poa": float(p_req[first]),
                "achieved": achieved,
                "grid": int(r.grid),
                "p_ne": float(worst[start + first]),
                "opt_p": float(opt_p[start + first]),
                "opt_cost": float(opt_cost[start + first]),
            }))
        return out

    # .. FedAvg campaigns ....................................................

    def _campaign_task(self):
        if self._task is None:
            from repro.federated.tasks import synthetic_mlp_task
            self._task = synthetic_mlp_task()
        if self._opt is None:
            from repro.optim import sgd
            self._opt = sgd(0.15)
        return self._task, self._opt

    def _campaign_engine(self, n: int, statics: tuple):
        """The un-jitted→jitted :func:`build_campaign` engine per bucket
        family (shared across batch rungs — jit re-lowers per shape)."""
        key = (n, statics, self.backend)
        engine = self._engines.get(key)
        if engine is None:
            from repro.federated.campaign import build_campaign
            from repro.federated.simulation import FLConfig
            rounds, local_steps, bpc, target_acc, consecutive = statics
            task, opt = self._campaign_task()
            fl = FLConfig(n_clients=n, local_steps=local_steps,
                          batch_per_client=bpc, max_rounds=rounds,
                          target_acc=target_acc, consecutive=consecutive)
            engine = build_campaign(fl, *task.campaign_args(), opt,
                                    backend=self.backend, mesh=self.mesh,
                                    batch_axis=self.batch_axis)
            self._engines[key] = engine
        return engine

    def _dispatch_campaign(self, pens: list[_Pending]) -> list[Response]:
        req0: CampaignRequest = pens[0].request
        n = req0.n
        statics = (req0.rounds, req0.local_steps, req0.batch_per_client,
                   req0.target_acc, req0.consecutive)
        e_part_default = float(self._rates.e_participant_j)
        e_idle_default = float(self._rates.e_idle_j)
        out: list[Response] = []
        for start, end in _chunks(len(pens), self.max_batch):
            chunk = pens[start:end]
            rows = len(chunk)
            bucket = bucket_for(req0, rows, max_batch=self.max_batch,
                                backend=self.backend,
                                mesh_axes=self._mesh_axes)
            b = bucket.batch
            p = pad_batch(jnp.asarray([c.request.p for c in chunk],
                                      jnp.float64), rows, b)
            seeds = pad_batch(jnp.asarray([c.request.seed for c in chunk],
                                          jnp.uint32), rows, b)
            e_part = pad_batch(jnp.asarray(
                [c.request.e_participant_j if c.request.e_participant_j
                 is not None else e_part_default for c in chunk],
                jnp.float64), rows, b)
            e_idle = pad_batch(jnp.asarray(
                [c.request.e_idle_j if c.request.e_idle_j is not None
                 else e_idle_default for c in chunk], jnp.float64), rows, b)

            run_bucket = dataclasses.replace(bucket, family="campaign/run")
            hit = run_bucket in self._programs
            engine = self._campaign_engine(n, statics)
            prog = self._program(
                run_bucket, lambda: engine.lower(p, seeds, e_part, e_idle))
            self._emit_dispatch(bucket, rows, hit)
            res = self._run(prog, p, seeds, e_part, e_idle)
            res = jax.tree.map(lambda leaf: leaf[:rows], res)

            tracker, ledger, aoi = res["tracker"], res["ledger"], res["aoi"]
            converged_at = np.asarray(tracker.converged_at)
            per_node_j = np.asarray(ledger.per_node_j)
            counts = np.asarray(ledger.participation_counts)
            led_rounds = np.asarray(ledger.rounds)
            mean_aoi = np.asarray(aoi.mean_aoi)
            accs = np.asarray(res["accs"])
            max_rounds = statics[0]
            for i, pen in enumerate(chunk):
                conv = bool(converged_at[i] >= 0)
                realized = int(converged_at[i]) + 1 if conv else max_rounds
                denom = max(int(led_rounds[i]), 1)
                out.append(self._finish(pen, bucket, {
                    "converged": conv,
                    "rounds": realized,
                    "energy_wh": float(per_node_j[i].sum() / J_PER_WH),
                    "final_acc": float(accs[i, -1]),
                    "mean_aoi": float(mean_aoi[i]),
                    "participation_rate": float(
                        (counts[i] / denom).mean()),
                }))
        return out


def _chunks(total: int, size: int) -> list[tuple[int, int]]:
    """[(start, end), …] slices of at most ``size`` covering ``total``."""
    return [(s, min(s + size, total)) for s in range(0, total, size)]
