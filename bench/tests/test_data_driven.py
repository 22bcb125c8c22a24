"""A new configuration, traffic mix and per-layer metric are new files
and new entries: the harness runs them with no existing file edited."""
import json

from bench.tests.conftest import run_cell


def test_new_cell_from_files_only(copy):
    bench = copy / "bench"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    conf = json.loads((bench / "configs/ne-hetero-n50.json").read_text())
    conf.update(name="ne-hetero-n12", n_nodes=12, costs=[0.2, 3.0])
    (bench / "configs/ne-hetero-n12.json").write_text(json.dumps(conf))
    (bench / "traffic/sweep4.json").write_text(json.dumps(
        {"driver": "ne_sweep", "batch": 4, "pool": {"seed": 7, "batches": 2},
         "check_batches": 1,
         "check_within": 1}))
    (bench / "metrics/ne.calls.py").write_text(
        "def read(run):\n    return float(run.counters['calls'])\n")
    (bench / "limits/n12.sweep.json").write_text(json.dumps(
        {"cert_gap": {"limit": 1e-9}}))
    spec = json.loads((copy / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "ne-hetero-n12", "source": "test",
                            "file": "bench/configs/ne-hetero-n12.json",
                            "reduced": ["n_nodes"], "why": "test"})
    spec["workloads"].append({"name": "n12.sweep", "config": "ne-hetero-n12",
                              "traffic": "sweep4", "chips": 1, "why": "t"})
    spec["end_to_end"][[m["name"] for m in spec["end_to_end"]].index(
        "fleets_per_s")]["workloads"].append("n12.sweep")
    spec["per_layer"].append({"name": "ne.calls", "unit": "calls",
                              "better": "higher", "source": "program_counter",
                              "layer": "game engine", "moves": "fleets_per_s",
                              "workloads": ["n12.sweep"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(spec))
    try:
        rc, last, err = run_cell(copy, "n12.sweep", seconds=1.0)
        assert rc == 0, err
        assert last["correct"] is True
        assert set(last["metrics"]) == {"setup_s", "fleets_per_s"}
        assert last["attempted"] % 4 == 0 and last["attempted"] > 0
        rc, last, err = run_cell(copy, "n12.sweep", seconds=1.0, trace=1)
        assert rc == 0, err
        assert last["metrics"]["ne.calls"]["value"] >= 1
        assert list(last)[-1] == "checks"
        for p, data in before.items():
            assert p.read_bytes() == data, f"{p} was edited"
    finally:
        for name in ("configs/ne-hetero-n12.json", "traffic/sweep4.json",
                     "metrics/ne.calls.py", "limits/n12.sweep.json"):
            (bench / name).unlink()
