"""Sweep-service driver: serve a JSONL request stream through
:class:`repro.serve.SweepService`.

The scenario-sweep twin of :mod:`repro.launch.serve` (the token-decode
driver): reads schema-versioned requests (one JSON object per line),
serves them through the padded/bucketed engines, writes one response per
line, and prints the service's cache/latency summary.

Usage:
  python -m repro.launch.serve_sweeps --input requests.jsonl --output -
  python -m repro.launch.serve_sweeps --demo 24 --events serve_events.jsonl
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

from repro.launch.compile_cache import enable_compile_cache
from repro.obs import EventSink
from repro.serve import SweepService
from repro.serve.workload import synthetic_workload


def _load_requests(path: str) -> list[dict]:
    out = []
    text = (sys.stdin.read() if path == "-"
            else pathlib.Path(path).read_text())
    for line in text.splitlines():
        line = line.strip()
        if line:
            out.append(json.loads(line))
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--input", help="requests JSONL ('-' for stdin)")
    ap.add_argument("--demo", type=int, default=0, metavar="N",
                    help="serve N synthetic mixed requests instead")
    ap.add_argument("--output", default="-",
                    help="responses JSONL ('-' for stdout)")
    ap.add_argument("--events", help="optional EventSink JSONL path")
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--backend", default=None,
                    choices=(None, "ref", "pallas"))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.demo:
        payloads = synthetic_workload(args.demo, seed=args.seed)
    elif args.input:
        payloads = _load_requests(args.input)
    else:
        ap.error("one of --input or --demo is required")

    enable_compile_cache()
    sink = None
    if args.events:
        # the sink appends; the driver owns the file, so start it fresh
        pathlib.Path(args.events).unlink(missing_ok=True)
        sink = EventSink(args.events)

    t0 = time.perf_counter()
    with SweepService(backend=args.backend, max_batch=args.max_batch,
                      sink=sink) as svc:
        responses = svc.serve(payloads)
        elapsed = time.perf_counter() - t0
        stats = svc.stats()

    lines = "\n".join(json.dumps(r.to_dict()) for r in responses) + "\n"
    if args.output == "-":
        sys.stdout.write(lines)
    else:
        pathlib.Path(args.output).write_text(lines)

    ok = sum(r.ok for r in responses)
    lat = stats.get("latency", {})
    print(f"served {len(responses)} responses ({ok} ok, "
          f"{len(responses) - ok} rejected) in {elapsed:.2f}s "
          f"({len(responses) / max(elapsed, 1e-9):.1f} req/s)",
          file=sys.stderr)
    print(f"cache: {stats['cache']['hits']} hits / "
          f"{stats['cache']['misses']} misses over "
          f"{stats['dispatches']} dispatches; padding overhead "
          f"{stats['padding_overhead']:.1%}; p50 latency "
          f"{lat.get('p50_us', float('nan')) / 1e3:.1f} ms",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
