"""The control (the reference in the nearest lower precision, in the
program's place) must fail the cell's check. CPU sizes: full N=50 fleets
for the game, a width-8 ResNet-18 for the campaign."""
import json

import pytest

from bench import control, run
from bench.tests.conftest import ROOT


def _ctx(workload, root=ROOT):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell, config, traffic, limits = run._cell(bench, workload)
    return run.Context(cell, config, traffic, limits, 0, 0.0, False, [])


@pytest.fixture(scope="module", autouse=True)
def _x64():
    import sys
    sys.path.insert(0, str(ROOT / "src"))
    import repro.core  # noqa: F401


def test_equilibrium_control_fails():
    ctx = _ctx("ne50.sweep")
    out = control.equilibrium(ctx, 2_147_483_659, fleets=4)
    limit = ctx.limits["cert_gap"]["limit"]
    assert out["control"]["cert_gap"] > limit
    assert out["altered"]["deviation"] > ctx.config["cert_tol"]


def test_campaign_control_fails(copy):
    ctx = _ctx("resnet18.tableII")
    small = _ctx("resnet18.tableII", root=copy)
    ctx.config = small.config
    out = control.campaign(ctx, 2_147_483_659)["param_change"]
    assert out["control"] > ctx.limits["param_change"]["limit"]
