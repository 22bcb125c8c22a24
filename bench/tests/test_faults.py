"""A run whose timed path is broken underneath must come out not correct.

Each test plants one fault in the program, in a CPU-sized copy, and
drives a whole run past the look for a chip.
"""
import pytest

from bench.tests.conftest import run_cell

UNCHANGED = """
import jax.numpy as jnp
import repro.federated.campaign as campaign
campaign.make_local_train = lambda loss_fn, opt: (
    lambda params, batches: (params, jnp.zeros(())))
"""

MERGE_ALTERED = """
import jax
import repro.federated.campaign as campaign
_merge = campaign.fedavg_merge
def fedavg_merge(g, c, mask, *a, **k):
    m = _merge(g, c, mask, *a, **k)
    return jax.tree.map(lambda x, y: x + 2.0 * (y - x), g, m)
campaign.fedavg_merge = fedavg_merge
"""

HALF_BATCH = """
import repro.models.resnet as resnet
_loss = resnet.loss_fn
def loss_fn(params, batch, norm="groupnorm"):
    half = batch["labels"].shape[0] // 2
    return _loss(params, {k: v[:half] for k, v in batch.items()}, norm)
resnet.loss_fn = loss_fn
"""

CERT_ALTERED_SWEEP = """
import repro.core
_verify = repro.core.verify_equilibrium_batched
repro.core.verify_equilibrium_batched = (
    lambda *a, **k: 0.5 * _verify(*a, **k))
"""


@pytest.mark.parametrize("workload, patch, number", [
    ("resnet18.tableII", UNCHANGED, "param_change"),
    ("resnet18.tableII", MERGE_ALTERED, "param_change"),
    ("resnet18.tableII", HALF_BATCH, "param_change"),
    ("ne50.sweep", CERT_ALTERED_SWEEP, "cert_gap"),
], ids=["state_unchanged", "merge_altered", "half_batch",
        "sweep_certificate_altered"])
def test_fault_is_not_correct(copy, workload, patch, number):
    rc, last, err = run_cell(copy, workload, patch=patch)
    assert rc == 0, err
    assert last["correct"] is False
    check = last["checks"][number]
    assert not check["value"] <= check["limit"], check


@pytest.mark.parametrize("workload", ["resnet18.tableII", "ne50.sweep"])
def test_sound_run_is_correct(copy, workload):
    rc, last, err = run_cell(copy, workload)
    assert rc == 0, err
    assert last["correct"] is True, last["checks"]
    assert last["failed"] == 0
