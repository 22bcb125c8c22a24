#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. It names a
configuration (``configs``: a file of sizes) and a traffic mix
(``bench/traffic/<traffic>.json``), whose ``driver`` key names the module
of ``bench/drivers`` that runs it. Per-layer metrics are readers in
``bench/metrics/<metric>.py``; the limits of the correctness check are in
``bench/limits/<cell>.json``. Everything is found by name, so a new cell,
configuration, traffic mix or metric is a new file and a new entry.

A run: make the inputs from ``--seed``, build and warm up every program
the traffic uses (``setup_s``), measure for ``--seconds`` with nothing
compiling, read the device's peak memory, free the program's state, check
the timed path's results against the plain reference, and print, as the
last line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``, then ``checks``: each number compared, beside its limit.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result. It keeps JAX's persistent compilation cache in
``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"


class Unavailable(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


class Context:
    """What a driver and a metric reader see of the run."""

    def __init__(self, workload: dict, config: dict, traffic: dict,
                 limits: dict, seed: int, seconds: float, trace: bool,
                 devices: list):
        self.workload = workload
        self.name = workload["name"]
        self.config = config
        self.traffic = traffic
        self.limits = limits
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.devices = devices
        self.chips = workload["chips"]
        # Set for the per-layer metric readers of a traced run.
        self.peaks = self.reduced = self.counters = None


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _cell(bench: dict, name: str) -> tuple[dict, dict, dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench/run.py: no workload {name!r} in "
                         f"BENCHMARK.json ({sorted(cells)})")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = _load_json(ROOT / conf["file"])
    traffic = _load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = _load_json(BENCH / "limits" / f"{name}.json")
    return cell, config, traffic, limits


def _metrics_for(bench: dict, name: str) -> tuple[list[dict], list[dict]]:
    """The cell's end-to-end metrics and per-layer metrics."""
    def listed(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if listed(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return e2e, layer


def _reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _start_jax(chips: int, require_chip: bool):
    """Pin the compilation cache to the checkout, then start JAX."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    platform = devices[0].platform
    if require_chip and platform != "tpu":
        raise Unavailable(f"needs a TPU, but JAX found platform "
                          f"{platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise Unavailable(f"the cell asks for {chips} chips, JAX found "
                          f"{len(devices)} {platform} devices")
    return jax, devices[:chips]


def _peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(argv: list[str] | None = None, *, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = _load_json(ROOT / "BENCHMARK.json")
    cell, config, traffic, limits = _cell(bench, args.workload)
    e2e, layer = _metrics_for(bench, args.workload)
    jax, devices = _start_jax(cell["chips"], require_chip)
    peaks = _load_json(BENCH / "peaks.json")["devices"].get(
        devices[0].device_kind)
    if peaks is None and require_chip:
        raise KeyError(f"device kind {devices[0].device_kind!r} is not in "
                       f"bench/peaks.json")
    sys.path.insert(0, str(ROOT / "src"))
    import repro.core  # noqa: F401  (the system under test: x64 on)

    from bench import trace as tracing
    from bench.compile_guard import CompileLog

    log = CompileLog()
    ctx = Context(cell, config, traffic, limits, args.seed, args.seconds,
                  bool(args.trace), devices)
    driver = importlib.import_module(
        f"bench.drivers.{traffic['driver']}").Driver(ctx)
    driver.setup()
    setup_s = time.perf_counter() - T0
    print(f"setup {setup_s:.1f} s: {log.compiles} compiles and cache loads "
          f"({log.seconds:.1f} s), cache hits {log.hits}, misses "
          f"{log.misses}", file=sys.stderr, flush=True)

    seconds = args.seconds
    trace_dir = None
    if args.trace:
        seconds = min(seconds, traffic.get("trace_seconds", seconds))
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=tracing.options())
    try:
        with log.guard(f"{args.workload} window"):
            with jax.profiler.TraceAnnotation(tracing.WINDOW):
                counters = driver.window(seconds)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    reduced = None
    if trace_dir:
        t_load = time.perf_counter()
        events = tracing.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        reduced = tracing.reduce(events, driver.scope_maps())
        print(f"trace: {tracing.describe(events)}; read in "
              f"{time.perf_counter() - t_load:.1f} s", file=sys.stderr)
        if reduced and reduced["truncated_s"]:
            print(f"trace: the profiler lost the window's last "
                  f"{reduced['truncated_s']:.3f} s; the traced window ends "
                  f"at the last recorded device op", file=sys.stderr)
    peak = _peak_bytes(devices)
    driver.release()
    checks = driver.check(counters)

    if args.trace:
        ctx.peaks, ctx.reduced, ctx.counters = peaks, reduced, counters
        metrics = {}
        for m in layer:
            value = _reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(driver.e2e(counters), setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in e2e}

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": all(c["ok"] for c in checks),
           "attempted": counters["attempted"], "failed": counters["failed"],
           "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


def main() -> int:
    sys.path[0] = str(ROOT)  # import the benchmark as the package `bench`
    try:
        return run()
    except Unavailable as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
