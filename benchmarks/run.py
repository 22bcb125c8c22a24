"""Benchmark entry point: every sweep, one command, schema'd artifacts.

``python benchmarks/run.py`` runs the full suite — paper-figure CSV rows,
the roofline analysis, both campaign sweeps, the kernel micro-benches, the
kernel-gap localization, and the instrumented obs smoke — and leaves the
``repro.obs/v1`` artifacts (``BENCH_*.json``, ``OBS_events.jsonl``,
``TRACE_*.json``) in the working directory, then schema-validates the lot
(the same gate CI runs via ``tools/obs_report.py --check``).

Select subsets with ``--only``::

    PYTHONPATH=src:. python benchmarks/run.py --only kernels,kernel_gap
    PYTHONPATH=src:. python benchmarks/run.py --list
"""
from __future__ import annotations

import argparse
import sys

# Every suite runs in this one process: a child started after the parent
# has touched JAX could not reach a chip the parent holds. The sharded
# suite meshes over 8 faked CPU devices on a CPU host, so the flag has to
# be set before JAX starts; it only touches the host platform.
from benchmarks.host_devices import fake_host_devices

fake_host_devices(8)

from benchmarks.common import header  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

#: name -> (runner, artifacts it emits). Order is the run order: cheap
#: smoke/figure rows first, the campaign sweeps (slowest) last.
SUITES: dict[str, tuple] = {}


def _suite(name, artifacts):
    def deco(fn):
        SUITES[name] = (fn, artifacts)
        return fn
    return deco


@_suite("figures", ())
def _figures() -> None:
    from benchmarks import paper_figures
    paper_figures.run_all()


@_suite("roofline", ())
def _roofline() -> None:
    from benchmarks import roofline
    roofline.run(emit_rows=True)


@_suite("kernels", ("BENCH_kernels.json",))
def _kernels() -> None:
    from benchmarks import kernels_micro
    kernels_micro.main([])


@_suite("kernel_gap", ("BENCH_kernel_gap.json",))
def _kernel_gap() -> None:
    from benchmarks import kernel_gap
    kernel_gap.main([])


@_suite("obs_smoke", ("BENCH_obs_smoke.json", "OBS_events.jsonl",
                      "TRACE_obs_smoke.json"))
def _obs_smoke() -> None:
    from benchmarks import obs_smoke
    obs_smoke.main([])


@_suite("serve", ("BENCH_serve.json", "OBS_serve_events.jsonl"))
def _serve() -> None:
    from benchmarks import serve_load
    serve_load.main([])


@_suite("ne_sweep", ())
def _ne_sweep() -> None:
    from benchmarks import heterogeneous_sweep
    heterogeneous_sweep.main([])


@_suite("mechanisms", ())
def _mechanisms() -> None:
    from benchmarks import mechanisms_sweep
    mechanisms_sweep.main([])


@_suite("coalition", ("BENCH_coalition.json",))
def _coalition() -> None:
    from benchmarks import coalition_sweep
    coalition_sweep.main([])


@_suite("campaign", ("BENCH_campaign.json",))
def _campaign() -> None:
    from benchmarks import campaign_sweep
    campaign_sweep.main([])


@_suite("hetero", ("BENCH_hetero_campaign.json",))
def _hetero() -> None:
    from benchmarks import heterogeneous_campaign
    heterogeneous_campaign.main([])


@_suite("sharded", ("BENCH_sharded_campaign.json",))
def _sharded() -> None:
    from benchmarks import sharded_campaign
    sharded_campaign.main([])


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of suites to run")
    ap.add_argument("--list", action="store_true",
                    help="list suite names and exit")
    ap.add_argument("--no-check", action="store_true",
                    help="skip the artifact schema validation at the end")
    args = ap.parse_args(argv)

    if args.list:
        for name, (_, artifacts) in SUITES.items():
            print(f"{name}: {', '.join(artifacts) or '(CSV rows only)'}")
        return 0

    names = list(SUITES) if args.only is None else args.only.split(",")
    unknown = [n for n in names if n not in SUITES]
    if unknown:
        ap.error(f"unknown suite(s) {unknown}; choices: {list(SUITES)}")

    enable_compile_cache()
    header()
    emitted: list[str] = []
    for name in names:
        fn, artifacts = SUITES[name]
        print(f"\n== {name} ==", flush=True)
        fn()
        emitted += artifacts

    if emitted and not args.no_check:
        from tools.obs_report import check
        print("\n== artifact validation ==", flush=True)
        return check(emitted)
    return 0


if __name__ == "__main__":
    sys.exit(main())
