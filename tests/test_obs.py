"""Observability layer (`repro.obs`) tests.

The contracts this file pins:

* **no-op off-switch** — obs disabled (or absent) leaves the campaign
  engine's outputs bitwise-identical, and obs *enabled* must too (the
  metric stream and event taps are derived observables, never inputs);
* event-sink callbacks fire under ``jit``/``lax.scan`` in program order
  (``ordered=True``) and once per batch element under ``vmap``;
* the metric-stream pytree rides the scan carry and round-trips with the
  realized round count at its cursor;
* dispatch counters count (site, backend) resolutions and reset;
* artifact/events schema validation accepts what the emitters produce and
  rejects structurally broken documents.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core  # noqa: F401  (enables x64)
from repro.federated.campaign import ChurnConfig, run_campaigns
from repro.federated.simulation import FLConfig
from repro.federated.tasks import synthetic_mlp_task
from repro.kernels import ops
from repro.obs import EventSink, ObsConfig, SpanTracer, compile_stats
from repro.obs.export import (EVENT_SCHEMA, SCHEMA, make_artifact,
                              timing_stats, validate_artifact,
                              validate_events_jsonl, write_artifact)
from repro.obs.metrics import MetricStream, merge_norm
from repro.optim import sgd


# ---------------------------------------------------------------------------
# ObsConfig
# ---------------------------------------------------------------------------

def test_obs_config_flags():
    off = ObsConfig()
    assert not off.record_metrics and not off.emit_events
    on = ObsConfig(enabled=True)
    assert on.record_metrics and not on.emit_events
    with pytest.raises(ValueError):
        ObsConfig(enabled=True, events=True)          # needs a sink
    sink = EventSink()
    full = ObsConfig(enabled=True, events=True, sink=sink)
    assert full.record_metrics and full.emit_events


# ---------------------------------------------------------------------------
# EventSink: callbacks under jit / scan / vmap
# ---------------------------------------------------------------------------

def test_events_ordered_under_jit_scan():
    """ordered=True taps inside a scanned jit arrive in program order."""
    sink = EventSink()

    @jax.jit
    def prog(x0):
        def step(c, i):
            c = c + i
            sink.tap("step", ordered=True, i=i, total=c)
            return c, c
        return jax.lax.scan(step, x0, jnp.arange(5, dtype=jnp.int32))[0]

    out = prog(jnp.int32(0))
    sink.flush()
    evs = sink.events
    assert [e["event"] for e in evs] == ["step"] * 5
    assert [e["i"] for e in evs] == list(range(5))
    assert [e["total"] for e in evs] == [0, 1, 3, 6, 10]
    assert [e["seq"] for e in evs] == list(range(5))
    assert int(out) == 10


def test_events_per_element_under_vmap():
    """Under vmap the tap fires once per batch element, unbatched values."""
    sink = EventSink()

    def one(tag, x):
        y = x * 2
        sink.tap("elem", tag=tag, y=y)
        return y

    jax.block_until_ready(
        jax.jit(jax.vmap(one))(jnp.arange(3), jnp.arange(3.0)))
    sink.flush()
    evs = sink.events
    assert len(evs) == 3
    assert sorted(e["tag"] for e in evs) == [0, 1, 2]
    for e in evs:
        assert e["y"] == pytest.approx(e["tag"] * 2.0)


def test_tap_valid_mask_filters_events():
    """tap(valid=...) drops events whose mask lands False — the hook the
    mesh path uses so padding-replica lanes (scenario_id = -1) never reach
    the stream."""
    sink = EventSink()

    def one(sid, x):
        sink.tap("elem", valid=sid >= 0, sid=sid, x=x)
        return x * 2

    jax.block_until_ready(
        jax.jit(jax.vmap(one))(jnp.asarray([0, -1, 2, -1]),
                               jnp.arange(4.0)))
    sink.flush()
    assert sorted(e["sid"] for e in sink.events) == [0, 2]
    # valid=None (the default) still emits unconditionally
    sink2 = EventSink()
    jax.block_until_ready(
        jax.jit(lambda x: (sink2.tap("e", x=x), x)[1])(jnp.float32(1)))
    sink2.flush()
    assert len(sink2.events) == 1


def test_disabled_sink_stages_nothing():
    """A disabled sink's tap must not even enter the traced program."""
    sink = EventSink(enabled=False)
    traced = jax.make_jaxpr(
        lambda x: (sink.tap("ev", x=x), x + 1)[1])(jnp.float32(0))
    assert "callback" not in str(traced)
    assert len(sink) == 0


def test_event_sink_writes_valid_jsonl(tmp_path):
    path = tmp_path / "events.jsonl"
    with EventSink(path) as sink:
        sink.emit("start", n=2)
        jax.block_until_ready(
            jax.jit(lambda x: (sink.tap("mid", x=x), x)[1])(jnp.arange(3)))
        sink.flush()
        sink.emit("end")
    lines = path.read_text().splitlines()
    assert validate_events_jsonl(lines) == []
    mid = json.loads(lines[1])
    assert mid["schema"] == EVENT_SCHEMA and mid["x"] == [0, 1, 2]


def test_event_sink_two_sinks_interleave_one_path(tmp_path):
    """Two sinks sharing a path append whole records — neither truncates
    the other's stream (append mode + per-record flush)."""
    path = tmp_path / "shared.jsonl"
    with EventSink(path) as a, EventSink(path) as b:
        for i in range(5):
            a.emit("from_a", i=i)
            b.emit("from_b", i=i)
    records = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(records) == 10
    # per-sink seq streams are intact and monotonic
    assert [r["seq"] for r in records if r["event"] == "from_a"] == \
        list(range(5))
    assert [r["seq"] for r in records if r["event"] == "from_b"] == \
        list(range(5))
    # per-record flush preserves emission order across the two sinks
    assert [(r["event"], r["i"]) for r in records] == \
        [(e, i) for i in range(5) for e in ("from_a", "from_b")]


# ---------------------------------------------------------------------------
# MetricStream
# ---------------------------------------------------------------------------

def test_metric_stream_roundtrip_through_scan():
    """The stream pytree rides a scan carry; cursor == recorded rounds."""
    def step(stream, r):
        rec = stream.record(participants=r, merge_norm=jnp.float32(r) / 10,
                            ledger_delta_j=jnp.float64(r) * 2.0,
                            accuracy=jnp.float32(0.5))
        return rec, None

    stream0 = MetricStream.create(6)
    out, _ = jax.jit(lambda s: jax.lax.scan(step, s, jnp.arange(4)))(stream0)
    assert int(out.cursor) == 4
    np.testing.assert_array_equal(np.asarray(out.participants),
                                  [0, 1, 2, 3, 0, 0])
    np.testing.assert_allclose(np.asarray(out.ledger_delta_j),
                               [0.0, 2.0, 4.0, 6.0, 0.0, 0.0])


def test_merge_norm_is_global_l2():
    a = {"w": jnp.zeros((2, 2)), "b": jnp.zeros((3,))}
    b = {"w": jnp.full((2, 2), 2.0), "b": jnp.full((3,), 1.0)}
    np.testing.assert_allclose(float(merge_norm(b, a)),
                               np.sqrt(4 * 4.0 + 3 * 1.0), rtol=1e-6)


# ---------------------------------------------------------------------------
# campaign integration: the hard bitwise contract
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_campaign():
    task = synthetic_mlp_task()
    fl = FLConfig(n_clients=5, local_steps=1, batch_per_client=8,
                  max_rounds=8, target_acc=0.73, seed=3)
    ps = jnp.asarray([0.35, 0.8], jnp.float32)
    base = run_campaigns(fl, *task.campaign_args(), sgd(0.15), ps)
    return task, fl, ps, base


def test_campaign_obs_disabled_is_bitwise_noop(small_campaign):
    task, fl, ps, base = small_campaign
    res = run_campaigns(fl, *task.campaign_args(), sgd(0.15), ps,
                        obs=ObsConfig(enabled=False))
    np.testing.assert_array_equal(np.asarray(res.acc_history),
                                  np.asarray(base.acc_history))
    np.testing.assert_array_equal(np.asarray(res.ledger.per_node_j),
                                  np.asarray(base.ledger.per_node_j))
    assert res.metrics is None


def test_campaign_obs_enabled_is_bitwise_and_streams(small_campaign):
    task, fl, ps, base = small_campaign
    res = run_campaigns(fl, *task.campaign_args(), sgd(0.15), ps,
                        obs=ObsConfig(enabled=True))
    np.testing.assert_array_equal(np.asarray(res.acc_history),
                                  np.asarray(base.acc_history))
    np.testing.assert_array_equal(np.asarray(res.ledger.per_node_j),
                                  np.asarray(base.ledger.per_node_j))
    m = res.metrics
    np.testing.assert_array_equal(np.asarray(m.cursor),
                                  np.asarray(base.rounds))
    # stream contents cross-check the engine's own outputs
    for b in range(len(ps)):
        r = int(base.rounds[b])
        np.testing.assert_array_equal(np.asarray(m.participants[b, :r]),
                                      np.asarray(base.k_history[b, :r]))
        np.testing.assert_array_equal(np.asarray(m.accuracy[b, :r]),
                                      np.asarray(base.acc_history[b, :r]))
    # per-round ledger deltas integrate exactly to the final ledger
    np.testing.assert_allclose(
        np.asarray(jnp.sum(m.ledger_delta_j, axis=1)),
        np.asarray(base.ledger.total_j), rtol=0, atol=0)
    summary = m.summary()
    assert summary["rounds"] == [int(r) for r in base.rounds]
    assert json.dumps(summary)                        # JSON-able


def test_campaign_events_bitwise_and_content(small_campaign):
    task, fl, ps, base = small_campaign
    with EventSink() as sink:
        res = run_campaigns(fl, *task.campaign_args(), sgd(0.15), ps,
                            obs=ObsConfig(enabled=True, events=True,
                                          sink=sink))
        jax.block_until_ready(res.acc_history)
        sink.flush()
        evs = sink.events
    np.testing.assert_array_equal(np.asarray(res.acc_history),
                                  np.asarray(base.acc_history))
    rounds = [e for e in evs if e["event"] == "round"]
    finals = [e for e in evs if e["event"] == "campaign"]
    assert len(rounds) == len(ps) * fl.max_rounds
    assert len(finals) == len(ps)
    for e in finals:
        b = e["scenario"]
        # converged_at is the round index, -1 if the campaign ran out
        want = (int(base.rounds[b]) - 1 if bool(base.converged[b]) else -1)
        assert e["converged_at"] == want
    for e in rounds:
        b, r = e["scenario"], e["round"]
        if e["active"]:
            assert e["participants"] == int(base.k_history[b, r])


def test_campaign_obs_with_churn(small_campaign):
    """Metrics slot in behind the churn carry entries without collision."""
    task, fl, ps, _ = small_campaign
    churn = ChurnConfig(arrival=0.5, departure=0.05)
    p_mat = jnp.tile(ps[:, None], (1, fl.n_clients))
    base = run_campaigns(fl, *task.campaign_args(), sgd(0.15), p_mat,
                         churn=churn)
    res = run_campaigns(fl, *task.campaign_args(), sgd(0.15), p_mat,
                        churn=churn, obs=ObsConfig(enabled=True))
    np.testing.assert_array_equal(np.asarray(res.acc_history),
                                  np.asarray(base.acc_history))
    np.testing.assert_array_equal(np.asarray(res.present_counts),
                                  np.asarray(base.present_counts))
    np.testing.assert_array_equal(np.asarray(res.metrics.cursor),
                                  np.asarray(base.rounds))


# ---------------------------------------------------------------------------
# dispatch stats (trace-time counters)
# ---------------------------------------------------------------------------

def test_dispatch_stats_from_real_call_sites():
    ops.reset_dispatch_stats()
    p = jnp.full((2, 6), 0.4)
    from repro.core.poibin import poibin_pmf_batched
    jax.block_until_ready(poibin_pmf_batched(p))
    jax.block_until_ready(poibin_pmf_batched(p, backend="pallas"))
    stats = ops.dispatch_stats()
    assert stats["poibin.pmf_batched"] == {"pallas": 1, "ref": 1}
    # explicit-pallas route re-dispatches through the ops wrapper
    assert stats["ops.poibin_pmf"] == {"pallas": 1}
    ops.reset_dispatch_stats()
    assert ops.dispatch_stats() == {}


def test_dispatch_counts_once_under_shard_map():
    """Per-call-site counters are trace-time: a shard_map body traces once,
    so the count must be 1 — not once per device replica. Runs over every
    device the process has (8 in the multi-device CI job)."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("data",))

    def body(x):
        ops.resolve_backend(None, default="ref", site="test.shard_map_site")
        return x * 2

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                       check_vma=False)
    ops.reset_dispatch_stats()
    x = jnp.arange(jax.device_count() * 2.0)
    jax.block_until_ready(jax.jit(fn)(x))
    assert ops.dispatch_stats()["test.shard_map_site"] == {"ref": 1}
    ops.reset_dispatch_stats()


def test_metrics_and_dispatch_once_under_mesh(small_campaign):
    """The sharded campaign engine: MetricStream bitwise vs unsharded and
    the merge dispatch counter counting the trace once (no per-replica
    double-count)."""
    from jax.sharding import Mesh

    task, fl, ps, base = small_campaign
    mesh = Mesh(np.array(jax.devices()), ("data",))
    ref = run_campaigns(fl, *task.campaign_args(), sgd(0.15), ps,
                        obs=ObsConfig(enabled=True))
    ops.reset_dispatch_stats()
    res = run_campaigns(fl, *task.campaign_args(), sgd(0.15), ps,
                        mesh=mesh, obs=ObsConfig(enabled=True))
    assert ops.dispatch_stats()["server.fedavg_merge"] == {"ref": 1}
    ops.reset_dispatch_stats()
    for a, b in zip(jax.tree.leaves(res.metrics), jax.tree.leaves(ref.metrics)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(res.metrics.cursor),
                                  np.asarray(base.rounds))


# ---------------------------------------------------------------------------
# tracer + compile stats
# ---------------------------------------------------------------------------

def test_span_tracer_chrome_trace(tmp_path):
    tracer = SpanTracer(process_name="t")
    with tracer.span("outer", n=3):
        with tracer.span("inner"):
            pass
    tracer.instant("mark")
    trace = tracer.to_chrome_trace()
    names = [e["name"] for e in trace["traceEvents"]]
    assert names[0] == "process_name"           # metadata record
    assert {"outer", "inner", "mark"} <= set(names)
    outer = next(e for e in trace["traceEvents"] if e["name"] == "outer")
    inner = next(e for e in trace["traceEvents"] if e["name"] == "inner")
    assert outer["ph"] == "X" and outer["args"] == {"n": 3}
    assert inner["ts"] >= outer["ts"]
    assert inner["dur"] <= outer["dur"]
    p = tracer.save(tmp_path / "trace.json")
    assert json.loads(p.read_text())["traceEvents"]
    assert tracer.summary()["outer"]["count"] == 1
    # disabled tracer: still yields, records nothing
    off = SpanTracer(enabled=False)
    with off.span("x"):
        pass
    assert off.spans == []


def test_compile_stats_reports_cost_and_timing():
    stats = compile_stats(lambda x: jnp.dot(x, x), jnp.ones((64, 64)),
                          iters=3)
    assert stats["lower_s"] >= 0 and stats["compile_s"] > 0
    assert stats["execute"]["n"] == 3
    assert stats["flops"] >= 2 * 64 ** 3 * 0.9     # one 64^3 matmul
    assert stats["bytes_accessed"] > 0


# ---------------------------------------------------------------------------
# artifact schema
# ---------------------------------------------------------------------------

def test_artifact_roundtrip_and_validation(tmp_path):
    art = write_artifact(tmp_path / "BENCH_t.json", "unit_test",
                         {"timing": timing_stats([1e-3, 2e-3, 3e-3])},
                         seed=7, backend="ref")
    assert art["schema"] == SCHEMA and art["meta"]["seed"] == 7
    loaded = json.loads((tmp_path / "BENCH_t.json").read_text())
    assert validate_artifact(loaded) == []
    assert loaded["data"]["timing"]["n"] == 3
    assert loaded["data"]["timing"]["p50_us"] == pytest.approx(2000.0)


def test_validation_rejects_broken_artifacts():
    assert validate_artifact([]) != []
    assert any("schema" in p for p in validate_artifact(
        {"schema": "v0", "kind": "x", "meta": {}, "data": {}}))
    # incomplete timing block anywhere in data is an error
    art = make_artifact("x", {"t": {"p50_us": 1.0, "p95_us": 2.0}})
    assert any("timing block missing" in p for p in validate_artifact(art))
    # complete one is fine
    art = make_artifact("x", {"t": timing_stats([0.001])})
    assert validate_artifact(art) == []


def test_validation_rejects_broken_events():
    good = json.dumps({"schema": EVENT_SCHEMA, "event": "e",
                       "seq": 0, "ts_us": 1.0})
    assert validate_events_jsonl([good]) == []
    assert validate_events_jsonl([]) != []                    # empty stream
    assert validate_events_jsonl(["not json"]) != []
    bad_seq = [good, json.dumps({"schema": EVENT_SCHEMA, "event": "e",
                                 "seq": -1, "ts_us": 2.0})]
    assert any("seq" in p for p in validate_events_jsonl(bad_seq))


def test_timing_stats_shape():
    s = timing_stats([0.001] * 10)
    assert s == {"p50_us": 1000.0, "p95_us": 1000.0, "mean_us": 1000.0,
                 "min_us": 1000.0, "max_us": 1000.0, "n": 10}
    with pytest.raises(ValueError):
        timing_stats([])
