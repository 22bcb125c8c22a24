"""From a profiler trace to the numbers the per-layer metrics read.

A traced run writes JAX's profiler output (``*.xplane.pb``) for its timed
window. :func:`load` flattens it into plain lists, and :func:`reduce`
turns those into seconds:

* ``busy_s`` -- the union of the intervals in which an operation ran on a
  device, averaged over the devices; ``window_s`` -- the traced window,
  taken from the benchmark's own ``bench.window`` host span, and cut
  short where the trace lost its tail (``truncated_s``, below);
* ``program_s`` -- device time per jitted program (the ``XLA Modules``
  line, keyed by the program's name without its fingerprint);
* ``op_name_s`` -- device time per JAX op name (``jit(f)/scope/.../op``),
  found by mapping each operation's HLO instruction name through the
  compiled program's HLO text (:func:`op_names`); a loop, branch or call
  op, whose interval holds other ops, is left out of it;
* ``collective_s`` -- device time of all-reduce, all-gather,
  reduce-scatter, all-to-all and collective-permute operations;
* ``device_ops`` and ``idle_gaps`` -- the ten operations (by op name)
  that took most device time, and the ten longest idle gaps, each named by
  the benchmark's host span that overlaps it most.

Every time is in seconds and is a mean over the devices traced, so that
a four-chip cell reads per chip.

The TPU profiler drops whatever does not fit a 2 GB trace (about 6.3 M
device ops on a v5e; libtpu logs "Profiler dropped trace buffers ... to
accommodate proto serialization limit (2GB)"), and the dropped events
are the newest. A driver wraps each call that waits for its device work
in a ``bench.call`` host span, so a call whose span outlasts the last
recorded device op by more than ``TAIL_S`` lost its events: the window
then ends at that op, ``truncated_s`` says how much was cut, and the
programs and op names read up to that point only.
"""
from __future__ import annotations

import collections
import glob
import os
import re

_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
# Ops whose interval holds other ops (a loop, a branch, a call): they count
# towards busy time but not towards any op name.
_CONTAINERS = ("while", "conditional", "call")
_HLO_LINE = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = .*?op_name=\"([^\"]*)\"")
HOST_PREFIX = "bench."
WINDOW = "bench.window"
CALL = "bench.call"
# The host's tail of a call after its last device op: reading results
# back, well under a millisecond when the device ops were recorded.
TAIL_S = 0.02


def options():
    """Profiler options: device ops and the benchmark's host spans, no
    Python function tracing."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def _short(hlo_text: str) -> tuple[str, str]:
    """``('%fusion.3 = f32[..] fusion(...)...')`` -> ``('fusion.3',
    'fusion')``: the instruction name and its opcode."""
    head, _, rest = hlo_text.partition(" = ")
    name = head.strip().lstrip("%")
    m = _OPCODE.search(" " + rest)
    return name, (m.group(1) if m else name.split(".")[0])


def op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name -> JAX op name, from a compiled program's HLO text
    (``compiled.as_text()``)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def load(log_dir: str) -> dict:
    """Flatten the newest ``*.xplane.pb`` under ``log_dir``.

    Returns ``{"devices": [{"name", "ops": [[start_ns, end_ns, name,
    opcode]], "modules": [[start_ns, end_ns, name]]}], "host": [[start_ns,
    end_ns, name]]}``; host events are the benchmark's own spans only.
    """
    import jax

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        name, opcode = _short(e.name)
                        ops.append([e.start_ns, e.start_ns + e.duration_ns,
                                    name, opcode])
                elif line.name == "XLA Modules":
                    for e in line.events:
                        modules.append([e.start_ns,
                                        e.start_ns + e.duration_ns,
                                        e.name.split("(")[0]])
            if ops or modules:
                devices.append({"name": plane.name, "ops": ops,
                                "modules": modules})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.start_ns, e.start_ns + e.duration_ns,
                                     e.name])
    return {"devices": devices, "host": host}


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _module_of(ops, modules):
    """For each op, the name of the program whose interval holds it."""
    mods = sorted(modules)
    out, j = [], 0
    for s, _, _, _ in ops:
        while j + 1 < len(mods) and mods[j + 1][0] <= s:
            j += 1
        out.append(mods[j][2] if mods and mods[j][0] <= s <= mods[j][1]
                   else "")
    return out


def reduce(events: dict, scope_maps: dict[str, dict[str, str]] | None = None
           ) -> dict:
    """Reduce :func:`load`'s output to seconds (see the module docstring);
    None when no device plane was traced (a CPU run).

    ``scope_maps`` maps a program's name (as in ``program_s``) to
    :func:`op_names` of its HLO text.
    """
    scope_maps = scope_maps or {}
    windows = [h for h in events["host"] if h[2] == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} span in the trace")
    lo, hi = windows[-1][0], windows[-1][1]
    devs = events["devices"]
    if not devs:
        return None
    last_op = max((o[1] for d in devs for o in d["ops"] if o[0] < hi),
                  default=lo)
    call_end = max((min(h[1], hi) for h in events["host"]
                    if h[2] == CALL and h[0] < hi), default=lo)
    truncated = call_end - last_op > TAIL_S * 1e9
    window_end = hi
    if truncated:
        hi = last_op
    n = len(devs)
    busy = 0.0
    program = collections.Counter()
    op_name = collections.Counter()
    collective = 0.0
    gaps = []
    for dev in devs:
        ops = sorted((o for o in dev["ops"] if o[1] > lo and o[0] < hi),
                     key=lambda o: o[0])
        spans = [_clip(o[0], o[1], lo, hi) for o in ops]
        union = _union(spans)
        busy += sum(e - s for s, e in union)
        for s, e, name in dev["modules"]:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                program[name] += (e - s) / n
        owners = _module_of(ops, dev["modules"])
        for (s, e), op, mod in zip(spans, ops, owners):
            if op[3] in _CONTAINERS:
                continue
            label = scope_maps.get(mod, {}).get(op[2])
            op_name[label or f"{mod}:{op[3]}"] += (e - s) / n
            if op[3].startswith(_COLLECTIVES):
                collective += (e - s) / n
        edges = [lo] + [x for iv in union for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    host = [h for h in events["host"] if h[2] != WINDOW]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        best, label = 0.0, "host outside any benchmark span"
        for hs, he, name in host:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, label = ov, name
        named.append([label, (e - s) * 1e-9])
    top_ops = sorted(op_name.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) * 1e-9,
        "truncated_s": (window_end - hi) * 1e-9,
        "busy_s": busy / n * 1e-9,
        "devices": n,
        "program_s": {k: v * 1e-9 for k, v in program.items()},
        "op_name_s": {k: v * 1e-9 for k, v in op_name.items()},
        "collective_s": collective * 1e-9,
        "device_ops": [[k, v * 1e-9] for k, v in top_ops],
        "idle_gaps": named,
    }


def seconds_under(reduced: dict, scope: str) -> float:
    """Device seconds of the ops whose JAX op name holds ``scope``."""
    if reduced is None:
        return 0.0
    return sum(v for k, v in reduced["op_name_s"].items() if scope in k)


def seconds_of_programs(reduced: dict, *names: str) -> float:
    """Device seconds of the programs whose name holds one of ``names``."""
    if reduced is None:
        return 0.0
    return sum(v for k, v in reduced["program_s"].items()
               if any(n in k for n in names))


def describe(events: dict) -> str:
    """One line on what a trace holds: ops and programs per device, and
    how far the device ops reach into the benchmark's window."""
    win = [h for h in events["host"] if h[2] == WINDOW]
    lo, hi = (win[-1][0], win[-1][1]) if win else (0.0, 0.0)
    parts = []
    for dev in events["devices"]:
        ops = dev["ops"]
        last = max((o[1] for o in ops), default=lo)
        names = collections.Counter(m[2] for m in dev["modules"])
        parts.append(f"{dev['name']}: {len(ops)} ops, programs "
                     f"{dict(names)}, last op at "
                     f"{(last - lo) * 1e-9:.3f} s of {(hi - lo) * 1e-9:.3f} s")
    return "; ".join(parts) or "no device plane"
