"""Hypothesis property tests for the coalition-formation engine.

Invariants pinned here on random games:

* the jitted partition dynamics (``solve_partition``) reproduce the plain-loop
  Python oracle (``partition_equilibrium_reference``) on small fleets —
  same assignment, matching participation profiles;
* the grand-coalition configuration (M = 1) reduces **bitwise** to the
  existing heterogeneous-NE engine;
* every converged returned partition is certified: no node gains more
  than the tolerance budget by an in-coalition deviation or a coalition
  switch (``verify_partition_batched``);
* singleton partitions (cap = 1) are frozen by construction and their
  solo equilibria are monotone — weakly decreasing in cost, weakly
  increasing in the AoI weight γ (so participation collapses as γ → 0
  only through the duration/cost trade-off).

Heavier fleets run under the ``slow`` marker (nightly split).
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep: skip, don't die, without it
from hypothesis import example, given, settings, strategies as st  # noqa: E402

import repro.core as C
from repro.core.asymmetric_batched import solve_heterogeneous
from repro.core.coalition import (partition_equilibrium_reference,
                                  solve_partition, verify_partition_batched)

seeds = st.integers(0, 2 ** 31 - 1)


def _dur(n):
    return C.theoretical_duration(n_nodes=n, d_inf=30.0, slope=6.0)


def _fleet(rng, n, b=None):
    """Random game with jittered costs (ties would stress argmax order)."""
    shape = (n,) if b is None else (b, n)
    costs = jnp.asarray(rng.uniform(0.5, 8.0, shape)
                        + rng.uniform(1e-3, 1e-2, shape))
    gammas = jnp.asarray(rng.uniform(0.2, 1.0, shape))
    return costs, gammas


@settings(max_examples=4, deadline=None)
@given(st.integers(3, 4), seeds)
@example(n=4, seed=14118)
def test_engine_matches_python_oracle(n, seed):
    """Tier-1 smoke diff on tiny fleets; bigger fleets live in the
    ``slow`` variant. seed=14118 once failed here: the oracle swept a
    joined candidate coalition with the joiner last, so it picked another
    of the subgame's NEs than the engine's node-index sweep."""
    m = 2
    rng = np.random.default_rng(seed)
    dur = _dur(n)
    costs, gammas = _fleet(rng, n)
    sol = solve_partition(costs, gammas, dur, n_coalitions=m)
    assign_ref, p_ref, conv_ref, switches_ref = (
        partition_equilibrium_reference(costs, gammas, dur, n_coalitions=m))
    assert bool(sol.converged[0]) == conv_ref
    if not conv_ref:
        return
    np.testing.assert_array_equal(np.asarray(sol.assign[0]),
                                  np.asarray(assign_ref))
    assert int(sol.switches[0]) == switches_ref
    np.testing.assert_allclose(np.asarray(sol.p[0]), np.asarray(p_ref),
                               atol=1e-5)


@settings(max_examples=8, deadline=None)
@given(st.integers(2, 8), seeds)
def test_grand_coalition_reduces_bitwise(n, seed):
    """M = 1 runs the same masked Gauss-Seidel program with an all-true
    mask, whose p·member pin is exact — bitwise equal to the asymmetric
    engine, not merely close."""
    rng = np.random.default_rng(seed)
    dur = _dur(n)
    costs, gammas = _fleet(rng, n, b=4)
    sol = solve_partition(costs, gammas, dur, n_coalitions=1)
    het = solve_heterogeneous(costs, gammas, dur)
    np.testing.assert_array_equal(np.asarray(sol.p), np.asarray(het.p))
    np.testing.assert_array_equal(np.asarray(sol.converged),
                                  np.asarray(het.converged))


@settings(max_examples=6, deadline=None)
@given(st.integers(2, 3), seeds)
def test_returned_partitions_are_certified(m, seed):
    n, b = 6, 6
    rng = np.random.default_rng(seed)
    dur = _dur(n)
    costs, gammas = _fleet(rng, n, b=b)
    sol = solve_partition(costs, gammas, dur, n_coalitions=m, tol=1e-10)
    conv = np.asarray(sol.converged & sol.inner_converged)
    assert conv.any()  # γ > 0 keeps best responses continuous: these settle
    dev = verify_partition_batched(costs, gammas, dur, sol.assign, sol.p,
                                   n_coalitions=m, tol=1e-10)
    assert np.all(np.asarray(dev)[conv] <= 1e-6), np.asarray(dev)


@settings(max_examples=8, deadline=None)
@given(st.floats(0.01, 0.2), st.floats(0.5, 1.0), seeds)
def test_singleton_partition_monotone_as_gamma_shrinks(g_lo, g_hi, seed):
    """cap = 1 singletons decouple the fleet into solo games. Each solo
    best response has increasing differences in (p, γ) — the AoI penalty
    is decreasing in p — so the equilibrium is weakly increasing in γ;
    and with equal γ it is weakly decreasing in cost."""
    n = 6
    rng = np.random.default_rng(seed)
    dur = _dur(n)
    costs = jnp.asarray(np.sort(rng.uniform(0.5, 8.0, n)))
    singles = jnp.arange(n, dtype=jnp.int32)

    def solo(gamma):
        sol = solve_partition(costs, jnp.full((n,), gamma), dur,
                              n_coalitions=n, cap=1, assign0=singles,
                              tol=1e-9)
        assert bool(sol.converged[0]) and int(sol.switches[0]) == 0
        return np.asarray(sol.p[0])

    p_lo, p_hi = solo(g_lo), solo(min(g_hi, g_lo + 1.0))
    if g_hi > g_lo:
        assert np.all(p_hi >= p_lo - 1e-6), (p_lo, p_hi)
    assert np.all(np.diff(p_lo) <= 1e-6), p_lo  # decreasing in cost


@pytest.mark.slow
@settings(max_examples=6, deadline=None)
@given(st.integers(4, 6), st.integers(2, 3), st.integers(1, 3), seeds)
@example(n=6, m=3, cap_slack=3, seed=235)
def test_engine_matches_oracle_with_caps_slow(n, m, cap_slack, seed):
    """Nightly: bigger fleets, capped slots, full oracle diff. The oracle
    runs at the default tolerance (it is a Python loop — a tight tol
    multiplies its sweeps); certification re-solves at tol=1e-10, where the
    corner residual ``tol/damping`` amplified by the boundary utility
    slope stays well under the 1e-6 budget."""
    rng = np.random.default_rng(seed)
    dur = _dur(n)
    costs, gammas = _fleet(rng, n)
    cap = min(n, -(-n // m) + cap_slack)  # ceil(n/m) + slack: feasible
    sol = solve_partition(costs, gammas, dur, n_coalitions=m, cap=cap)
    assign_ref, p_ref, conv_ref, _ = partition_equilibrium_reference(
        costs, gammas, dur, n_coalitions=m, cap=cap)
    assert bool(sol.converged[0]) == conv_ref
    if not conv_ref:
        return
    np.testing.assert_array_equal(np.asarray(sol.assign[0]),
                                  np.asarray(assign_ref))
    np.testing.assert_allclose(np.asarray(sol.p[0]), np.asarray(p_ref),
                               atol=1e-5)
    sizes = np.asarray(sol.sizes[0])
    assert sizes.sum() == n and np.all(sizes <= cap)
    tight = solve_partition(costs, gammas, dur, n_coalitions=m, cap=cap,
                            tol=1e-10)
    dev = verify_partition_batched(costs, gammas, dur, tight.assign, tight.p,
                                   n_coalitions=m, cap=cap, tol=1e-10)
    assert float(dev[0]) <= 1e-6
