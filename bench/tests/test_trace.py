"""The trace reduction, on a trace recorded on a TPU v5e and on a
hand-made two-device trace."""
import pytest

from bench import trace
from bench.tests.conftest import ROOT

# The recorded program: three calls of a jitted f holding a matmul under
# campaign/local_train, a multiply under campaign/merge and a 10-step
# while loop; the op names as its HLO metadata gives them.
RECORDED_OPS = {
    "fusion": "jit(f)/campaign/local_train/dot_general",
    "broadcast_multiply_fusion": "jit(f)/campaign/merge/mul",
    "sine_add_fusion.2": "jit(f)/while/body/add",
}


@pytest.fixture(scope="module")
def recorded():
    events = trace.load(str(ROOT / "bench/tests/data"))
    ops = events["devices"][0]["ops"]
    lo = min(o[0] for o in ops) - 1000.0
    hi = max(o[1] for o in ops) + 1000.0
    events["host"] = [[lo, hi, trace.WINDOW]]
    return events, lo, hi


def test_load_reads_device_ops_and_programs(recorded):
    events, _, _ = recorded
    (dev,) = events["devices"]
    assert dev["name"] == "/device:TPU:0"
    assert len(dev["ops"]) == 51
    assert [m[2] for m in dev["modules"]] == ["jit_f"] * 3
    kinds = {(o[2], o[3]) for o in dev["ops"]}
    assert ("while", "while") in kinds
    assert ("copy-start", "copy-start") in kinds


def test_reduce_recorded(recorded):
    events, lo, hi = recorded
    ops = events["devices"][0]["ops"]
    red = trace.reduce(events, {"jit_f": RECORDED_OPS})
    # Busy: the union of every op's interval, computed here by hand.
    spans = sorted((o[0], o[1]) for o in ops)
    union, (cur_s, cur_e) = 0.0, spans[0]
    for s, e in spans[1:]:
        if s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            union += cur_e - cur_s
            cur_s, cur_e = s, e
    union += cur_e - cur_s
    assert red["busy_s"] == pytest.approx(union * 1e-9)
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    dur = {}
    for o in ops:
        dur[o[2]] = dur.get(o[2], 0.0) + (o[1] - o[0]) * 1e-9
    assert trace.seconds_under(red, "campaign/local_train") == \
        pytest.approx(dur["fusion"])
    assert trace.seconds_under(red, "campaign/merge") == \
        pytest.approx(dur["broadcast_multiply_fusion"])
    # The loop op holds its body's ops and is left out of the op names.
    assert trace.seconds_under(red, "while/body") == \
        pytest.approx(dur["sine_add_fusion.2"])
    assert not any(k.endswith(":while") for k in red["op_name_s"])
    mods = sum(m[1] - m[0] for m in events["devices"][0]["modules"])
    assert red["program_s"]["jit_f"] == pytest.approx(mods * 1e-9)
    assert red["collective_s"] == 0.0
    assert red["device_ops"][0][0] == RECORDED_OPS["fusion"]


def test_reduce_two_devices_collectives_and_gaps():
    ms = 1_000_000.0
    events = {
        "devices": [
            {"name": "/device:TPU:0",
             "ops": [[0, 4 * ms, "fusion.1", "fusion"],
                     [4 * ms, 5 * ms, "all-reduce.1", "all-reduce"],
                     [8 * ms, 10 * ms, "fusion.2", "fusion"]],
             "modules": [[0, 10 * ms, "jit_step"]]},
            {"name": "/device:TPU:1",
             "ops": [[0, 2 * ms, "fusion.1", "fusion"],
                     [2 * ms, 5 * ms, "all-reduce.1", "all-reduce"]],
             "modules": [[0, 5 * ms, "jit_step"]]},
        ],
        "host": [[0, 12 * ms, trace.WINDOW],
                 [5 * ms, 8 * ms, "bench.inputs"],
                 [8.5 * ms, 12 * ms, "bench.poll"]],
    }
    red = trace.reduce(events, {"jit_step": {"fusion.1": "jit(step)/a/dot",
                                             "all-reduce.1": "jit(step)/psum"}})
    assert red["devices"] == 2
    assert red["window_s"] == pytest.approx(0.012)
    assert red["busy_s"] == pytest.approx((0.007 + 0.005) / 2)
    assert red["collective_s"] == pytest.approx((0.001 + 0.003) / 2)
    assert red["program_s"]["jit_step"] == pytest.approx((0.010 + 0.005) / 2)
    assert red["op_name_s"]["jit(step)/a/dot"] == pytest.approx(0.003)
    assert red["op_name_s"]["jit_step:fusion"] == pytest.approx(0.001)
    gaps = sorted(red["idle_gaps"], key=lambda g: -g[1])
    assert gaps[0] == ["bench.poll", pytest.approx(0.007)]
    assert ["bench.inputs", pytest.approx(0.003)] in gaps
    assert ["bench.poll", pytest.approx(0.002)] in gaps


def test_reduce_without_a_device_plane_is_none():
    assert trace.reduce({"devices": [], "host": [[0, 1, trace.WINDOW]]}) \
        is None


def test_short_and_op_names():
    assert trace._short("%fusion.3 = f32[8]{0:T(8)S(1)} fusion(f32[8] %a), "
                        "kind=kLoop") == ("fusion.3", "fusion")
    assert trace._short("%copy-start = (f32[4]{0}, u32[]) copy-start("
                        "f32[4]{0:T(4)} %x)") == ("copy-start", "copy-start")
    assert trace._short("%all-reduce.7 = f32[2]{0} all-reduce(f32[2] %p), "
                        "to_apply=%add")[1] == "all-reduce"
    hlo = ('  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
           'metadata={op_name="jit(f)/campaign/merge/mul" '
           'source_file="x.py" source_line=3}\n'
           '  ROOT %tuple = (f32[8]) tuple(f32[8] %fusion.3)\n')
    assert trace.op_names(hlo) == {"fusion.3": "jit(f)/campaign/merge/mul"}



def test_reduce_cuts_a_window_whose_tail_was_dropped():
    ms = 1_000_000.0
    events = {
        "devices": [{"name": "/device:TPU:0",
                     "ops": [[10 * ms, 60 * ms, "fusion.1", "fusion"]],
                     "modules": [[10 * ms, 60 * ms, "jit_solve"]]}],
        # The call waited on device work until 99 ms; none was recorded
        # after 60 ms.
        "host": [[0, 100 * ms, trace.WINDOW], [5 * ms, 99 * ms, trace.CALL]],
    }
    red = trace.reduce(events)
    assert red["window_s"] == pytest.approx(0.060)
    assert red["truncated_s"] == pytest.approx(0.040)
    assert red["busy_s"] == pytest.approx(0.050)
    # A call that returns within TAIL_S of its last device op is whole.
    events["host"][1] = [5 * ms, 60.5 * ms, trace.CALL]
    red = trace.reduce(events)
    assert red["window_s"] == pytest.approx(0.100)
    assert red["truncated_s"] == 0.0
