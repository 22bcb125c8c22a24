"""Hypothesis property tests for the batched heterogeneous-NE engine.

Invariants pinned here on random games:

* the batched engine reproduces the seed scalar Gauss-Seidel loop
  (``best_response_dynamics_reference``) on small games;
* every converged scenario in a vmapped batch is a certified NE
  (max profitable unilateral deviation ≤ 1e-4);
* identical-node batches reproduce the symmetric ``solve_symmetric_ne``
  equilibrium;
* participation is weakly decreasing in cost, except across a pair of
  nodes whose own participation gap sustains the inversion (free-rider
  stratification).
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep: skip, don't die, without it
from hypothesis import example, given, settings, strategies as st  # noqa: E402

import repro.core as C
from repro.core.asymmetric import (HeterogeneousGame,
                                   best_response_dynamics_reference)
from repro.core.asymmetric_batched import (solve_heterogeneous,
                                           verify_equilibrium_batched)
from repro.core.game import solve_symmetric_ne
from repro.core.poibin import poibin_pmf_recursive
from repro.core.utility import UtilityParams
from helpers import assert_heterogeneous_ne

seeds = st.integers(0, 2 ** 31 - 1)


def _dur(n):
    return C.theoretical_duration(n_nodes=n, d_inf=30.0, slope=6.0)


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 8), st.floats(0.5, 8.0), st.floats(0.1, 1.0), seeds)
def test_batched_matches_scalar_reference(n, cost_hi, gamma, seed):
    rng = np.random.default_rng(seed)
    dur = _dur(n)
    costs = jnp.asarray(rng.uniform(0.1, cost_hi, n))
    gammas = jnp.full((n,), gamma)
    game = HeterogeneousGame(costs=costs, gammas=gammas, dur=dur)
    p_ref, conv_ref, _ = best_response_dynamics_reference(game, damping=0.6,
                                                          max_iters=150)
    sol = solve_heterogeneous(costs, gammas, dur, damping=0.6, max_iters=150)
    p_new, conv_new, _ = sol.single()
    assert conv_new == conv_ref
    if conv_ref:
        np.testing.assert_allclose(np.asarray(p_new), np.asarray(p_ref),
                                   atol=1e-6)


@settings(max_examples=8, deadline=None)
@given(st.floats(0.1, 1.0), seeds)
def test_vmapped_batch_is_certified(gamma, seed):
    n, b = 6, 8
    rng = np.random.default_rng(seed)
    dur = _dur(n)
    costs = jnp.asarray(rng.uniform(0.1, 10.0, (b, n)))
    gammas = jnp.full((b, n), gamma)
    sol = solve_heterogeneous(costs, gammas, dur, damping=0.6, max_iters=300)
    dev = verify_equilibrium_batched(costs, gammas, dur, sol.p)
    conv = np.asarray(sol.converged)
    assert conv.any()  # γ > 0 keeps best responses continuous: these settle
    assert np.all(np.asarray(dev)[conv] <= 1e-4)


@settings(max_examples=8, deadline=None)
@given(st.floats(0.6, 1.0), st.floats(2.0, 5.0))
def test_identical_nodes_reproduce_symmetric_ne(gamma, cost):
    """In the region where the symmetric NE is stable under Gauss-Seidel
    (γ ≥ 0.6, moderate c), identical nodes land on the symmetric
    ``solve_symmetric_ne`` equilibrium. Outside it the dynamics can settle
    on *certified asymmetric* equilibria among identical nodes — see
    ``test_asymmetric_batched.test_identical_nodes_can_stratify``."""
    n = 20
    dur = _dur(n)
    costs = jnp.full((n,), cost)
    gammas = jnp.full((n,), gamma)
    sol = solve_heterogeneous(costs, gammas, dur, damping=0.6, max_iters=300)
    p, conv, _ = sol.single()
    if not conv:
        return
    assert_heterogeneous_ne(costs, gammas, dur, p, tol=1e-3)
    assert float(jnp.max(p) - jnp.min(p)) < 5e-3  # stays symmetric
    sym = solve_symmetric_ne(UtilityParams(gamma=gamma, cost=cost, n_nodes=n),
                             dur, grid_size=400)
    assert any(abs(float(jnp.mean(p)) - s) < 0.05 for s in sym), (
        float(jnp.mean(p)), sym)


@settings(max_examples=10, deadline=None)
@given(st.floats(0.1, 1.0), st.floats(1.0, 12.0), seeds)
@example(gamma=1.0, cost_hi=1.0, seed=0)
def test_participation_weakly_decreasing_in_cost(gamma, cost_hi, seed):
    """A cheaper node participates at least as much as a costlier one,
    unless the pair sustains stratification. With equal γ the best
    response rises in a = slope - cost, so p_i < p_j at c_i < c_j needs
    slope_j - slope_i > c_j - c_i; and exactly
    slope_j - slope_i = (p_j - p_i)·E[Δ²d(m_{-ij})], m_{-ij} counting
    the other nodes. seed=0 settles on such a certified NE: two nodes
    0.08 apart in cost at p ≈ 0.13 and p = 1
    (``test_identical_nodes_can_stratify`` is the equal-cost case). The
    solve runs at tol=1e-10: at the default tol, nodes at the p = 1 corner
    stop up to ~tol/damping short of it, apart by more than 1e-6."""
    n = 8
    rng = np.random.default_rng(seed)
    dur = _dur(n)
    costs = jnp.asarray(np.sort(rng.uniform(0.1, cost_hi, n)))
    gammas = jnp.full((n,), gamma)
    sol = solve_heterogeneous(costs, gammas, dur, damping=0.6, max_iters=300,
                              tol=1e-10)
    p, conv, _ = sol.single()
    if not conv:
        return
    assert_heterogeneous_ne(costs, gammas, dur, p)
    p, costs = np.asarray(p), np.asarray(costs)
    d = np.asarray(dur.table())
    d2 = d[2:] - 2.0 * d[1:-1] + d[:-2]                # Δ²d(k), k = 0..n-2
    inverted = np.triu(p[None, :] - p[:, None] > 1e-6, k=1)  # i < j
    for i, j in np.argwhere(inverted):
        rest = jnp.asarray(np.delete(p, [i, j]))
        pmf = np.asarray(poibin_pmf_recursive(rest))
        sustained = (p[j] - p[i]) * float(pmf @ d2)
        assert costs[j] - costs[i] < sustained, (i, j, p, sustained)
