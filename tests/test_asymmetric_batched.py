"""Batched heterogeneous-equilibrium engine vs the scalar seed oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as C
from repro.core.asymmetric import (HeterogeneousGame,
                                   best_response_dynamics,
                                   best_response_dynamics_reference,
                                   planner_coordinate_descent,
                                   verify_equilibrium,
                                   verify_equilibrium_reference)
from repro.core.asymmetric_batched import (P_MIN, best_response_given_slope,
                                           planner_batched, poa_report,
                                           social_cost_batched,
                                           solve_heterogeneous,
                                           verify_equilibrium_batched)
from repro.core.poibin import (poibin_convolve, poibin_pmf_loo,
                               poibin_pmf_recursive)
from helpers import (assert_heterogeneous_ne, direct_deviation,
                     gauss_seidel_reference)

N = 10


@pytest.fixture(scope="module")
def dur():
    return C.theoretical_duration(n_nodes=N, d_inf=35.0, slope=8.0)


@pytest.fixture(scope="module")
def game(dur):
    costs = jnp.asarray(np.linspace(0.5, 12.0, N))
    gammas = jnp.full((N,), 0.6)
    return HeterogeneousGame(costs=costs, gammas=gammas, dur=dur)


# ---- engine vs the eager seed loop ----------------------------------------

def test_engine_matches_reference_loop(game):
    p_ref, conv_ref, it_ref = best_response_dynamics_reference(game,
                                                               damping=0.6)
    p_new, conv_new, it_new = best_response_dynamics(game, damping=0.6)
    assert conv_ref and conv_new
    assert it_ref == it_new
    np.testing.assert_allclose(np.asarray(p_new), np.asarray(p_ref),
                               atol=1e-12)


# The N=50 fleets of the ne-hetero-n50 benchmark pool (costs U(0.5, 12), AoI
# weights U(0.2, 1), drawn as bench/inputs.ne_fleets draws batch j from
# fold_in(PRNGKey(2503), j)) on which an incremental pmf deconvolution
# drifted off the direct Gauss-Seidel path: (batch, fleet).
POOL_FLEETS = [(3, 195), (3, 38), (3, 325), (5, 109)]


# Pool fleets whose answers hold nodes at p = 1. Their certificates are
# the pool's most sensitive to the duration table: fitted on a v5e,
# the table moved them 1.4e-7 (2, 192) and 1.1e-8 (6, 173) off the direct
# certificate at the host's fit.
CERT_FLEETS = [(2, 192), (6, 173)]


def _pool_fleet(batch, fleet):
    key = jax.random.fold_in(jax.random.PRNGKey(2503), batch)
    kc, kg = jax.random.split(key)
    costs = jax.random.uniform(kc, (512, 50), jnp.float64, 0.5, 12.0)
    gammas = jax.random.uniform(kg, (512, 50), jnp.float64, 0.2, 1.0)
    return costs[fleet:fleet + 1], gammas[fleet:fleet + 1]


@pytest.fixture(scope="module")
def dur50():
    return C.theoretical_duration(n_nodes=50, d_inf=35.0, slope=4.0,
                                  horizon=500.0)


@pytest.mark.parametrize("batch,fleet", POOL_FLEETS)
def test_engine_matches_direct_gauss_seidel_n50(dur50, batch, fleet):
    """At N=50 the engine follows the direct Gauss-Seidel sweep (the
    others' pmf as a product of their factors at every node step): the
    same profile to 1e-10 after the same number of sweeps."""
    costs, gammas = _pool_fleet(batch, fleet)
    p_ref, conv_ref, it_ref = gauss_seidel_reference(costs, gammas,
                                                     dur50.table())
    sol = solve_heterogeneous(costs, gammas, dur50)
    assert bool(sol.converged[0]) == bool(conv_ref[0])
    assert int(sol.iters[0]) == int(it_ref[0])
    np.testing.assert_allclose(np.asarray(sol.p), p_ref, rtol=0, atol=1e-10)


@pytest.mark.parametrize("batch,fleet", CERT_FLEETS)
def test_verify_matches_direct_certificate_n50(dur50, batch, fleet):
    """The certificate of the engine's N=50 answer is the direct one: each
    node's opponents' pmf as a product of their factors, no deconvolution."""
    costs, gammas = _pool_fleet(batch, fleet)
    sol = solve_heterogeneous(costs, gammas, dur50)
    dev = verify_equilibrium_batched(costs, gammas, dur50, sol.p)
    want = direct_deviation(costs, gammas, dur50.table(), sol.p)
    assert 0.0 < want[0] < 1e-4
    np.testing.assert_allclose(np.asarray(dev), want, rtol=0, atol=1e-10)


def test_verify_matches_reference(game):
    p, conv, _ = best_response_dynamics(game, damping=0.6)
    assert conv
    dev_ref = verify_equilibrium_reference(game, p)
    dev_new = verify_equilibrium(game, p)
    assert dev_new == pytest.approx(dev_ref, abs=1e-9)
    assert dev_new <= 1e-4


def test_planner_matches_reference_fixed_point(game):
    """The jitted corner-selection planner lands on the same profile as the
    scalar grid-argmin (the social cost is linear per coordinate)."""
    p, conv, _ = best_response_dynamics(game, damping=0.6)
    assert conv
    p_opt = planner_coordinate_descent(game, p)
    cost_opt = float(game.social_cost(p_opt))
    assert cost_opt <= float(game.social_cost(p)) + 1e-9
    # every coordinate is a corner
    opt = np.asarray(p_opt)
    assert np.all((np.abs(opt - P_MIN) < 1e-12) | (np.abs(opt - 1.0) < 1e-12))


# ---- batching --------------------------------------------------------------

def test_vmapped_batch_all_certified(dur):
    rng = np.random.default_rng(3)
    b = 16
    costs = jnp.asarray(rng.uniform(0.5, 12.0, (b, N)))
    gammas = jnp.asarray(rng.uniform(0.2, 1.0, (b, N)))
    sol = solve_heterogeneous(costs, gammas, dur, damping=0.6, max_iters=300)
    assert bool(jnp.all(sol.converged))
    dev = verify_equilibrium_batched(costs, gammas, dur, sol.p)
    assert float(jnp.max(dev)) <= 1e-4
    # batch rows are independent: row i solved alone gives the same profile
    one = solve_heterogeneous(costs[3], gammas[3], dur, damping=0.6,
                              max_iters=300)
    np.testing.assert_allclose(np.asarray(one.p[0]), np.asarray(sol.p[3]),
                               atol=1e-12)


def test_poa_report_invariants(dur):
    rng = np.random.default_rng(4)
    b = 8
    costs = jnp.asarray(rng.uniform(0.5, 10.0, (b, N)))
    gammas = jnp.asarray(rng.uniform(0.3, 0.9, (b, N)))
    rep = poa_report(costs, gammas, dur, damping=0.6, max_iters=300)
    assert bool(jnp.all(rep.solution.converged))
    assert float(jnp.max(rep.deviation)) <= 1e-4
    # planner descent from the NE can only lower the cost → PoA ≥ 1
    assert bool(jnp.all(rep.poa >= 1.0 - 1e-9))
    np.testing.assert_allclose(
        np.asarray(rep.ne_cost),
        np.asarray(social_cost_batched(costs, dur, rep.solution.p)))


def test_batched_duration_tables(dur):
    """A (B, N+1) stack of per-scenario duration tables vmaps through."""
    d_tab = dur.table()
    tabs = jnp.stack([d_tab, d_tab * 1.5])
    costs = jnp.asarray(np.linspace(0.5, 8.0, N))
    sol = solve_heterogeneous(jnp.stack([costs, costs]),
                              jnp.full((2, N), 0.6), tabs, damping=0.6)
    assert bool(jnp.all(sol.converged))
    base = solve_heterogeneous(costs, jnp.full((N,), 0.6), d_tab, damping=0.6)
    np.testing.assert_allclose(np.asarray(sol.p[0]), np.asarray(base.p[0]),
                               atol=1e-12)
    # scaling d(k) raises the stakes of coordination → some profile change
    assert float(jnp.max(jnp.abs(sol.p[1] - sol.p[0]))) > 1e-6


def test_shape_validation(dur):
    with pytest.raises(ValueError):
        solve_heterogeneous(jnp.ones((2, N)), jnp.ones((3, N)), dur)
    with pytest.raises(ValueError):
        solve_heterogeneous(jnp.ones((N,)), jnp.ones((N,)),
                            jnp.ones((N + 5,)))


# ---- free-rider stratification & helper certification ----------------------

def test_participation_monotone_in_cost_batched(dur):
    rng = np.random.default_rng(5)
    costs = jnp.asarray(np.sort(rng.uniform(0.5, 12.0, (6, N)), axis=1))
    gammas = jnp.full((6, N), 0.6)
    sol = solve_heterogeneous(costs, gammas, dur, damping=0.6, max_iters=300)
    assert bool(jnp.all(sol.converged))
    assert bool(jnp.all(jnp.diff(sol.p, axis=1) <= 1e-6))
    for i in range(6):
        assert_heterogeneous_ne(costs[i], gammas[i], dur, sol.p[i])


def test_identical_nodes_can_stratify(dur):
    """Beyond-paper observation: for identical nodes outside the symmetric
    equilibrium's Gauss-Seidel stability region (here: weak incentive, high
    cost), the dynamics settle on a *certified asymmetric* NE — free-rider
    stratification emerges spontaneously without any cost heterogeneity."""
    costs = jnp.full((N,), 6.0)
    gammas = jnp.full((N,), 0.2)
    sol = solve_heterogeneous(costs, gammas, dur, damping=0.6, max_iters=300)
    p, conv, _ = sol.single()
    assert conv
    assert float(jnp.max(p) - jnp.min(p)) > 0.3  # genuinely stratified
    assert_heterogeneous_ne(costs, gammas, dur, p)


# ---- best-response closed form (division-guard regression) -----------------

def test_best_response_a_to_zero_limit():
    """Regression: the a → 0⁻ limit of the interior BR is p = 1, and the
    two-sided division guard keeps a = 0 exactly on the same value (the old
    one-sided `where(a < 0, a, -1e-9)` pushed a huge 2e9·γ `prod` through
    the a ≥ 0 branch)."""
    gamma = jnp.asarray(0.6)
    cost = jnp.asarray(0.0)
    # slope == cost → a == 0 exactly
    assert float(best_response_given_slope(jnp.asarray(0.0), cost,
                                           gamma)) == 1.0
    # approach from below: BR must be continuous into the limit
    for a in [-1e-12, -1e-9, -1e-6]:
        br = float(best_response_given_slope(jnp.asarray(a), cost, gamma))
        assert br == pytest.approx(1.0, abs=1e-3), a
    # and well inside the interior branch the stationary point is exact:
    # a = -2γ/(p(2-p)) at p = 0.5 → p* recovers 0.5
    a = -2.0 * 0.6 / (0.5 * 1.5)
    br = float(best_response_given_slope(jnp.asarray(a), cost, gamma))
    assert br == pytest.approx(0.5, abs=1e-12)


def test_best_response_gamma_zero_bang_bang():
    cost = jnp.asarray(0.0)
    zero = jnp.asarray(0.0)
    assert float(best_response_given_slope(jnp.asarray(2.0), cost,
                                           zero)) == 1.0
    assert float(best_response_given_slope(jnp.asarray(-2.0), cost,
                                           zero)) == P_MIN
    # exact indifference resolves to P_MIN like the scalar seed
    assert float(best_response_given_slope(jnp.asarray(0.0), cost,
                                           zero)) == P_MIN


def test_best_response_is_finite_everywhere():
    slopes = jnp.asarray([-1e6, -10.0, -1e-9, 0.0, 1e-9, 10.0, 1e6])
    for g in [0.0, 1e-9, 0.6, 5.0]:
        for c in [0.0, 2.0, 60.0]:
            br = best_response_given_slope(slopes, jnp.asarray(c),
                                           jnp.asarray(g))
            assert bool(jnp.all(jnp.isfinite(br)))
            assert bool(jnp.all((br >= P_MIN) & (br <= 1.0)))


# ---- leave-one-out deconvolution ------------------------------------------

def test_loo_deconvolution_inverts_convolution():
    rng = np.random.default_rng(6)
    p = jnp.asarray(rng.uniform(0, 1, 17))
    f = poibin_pmf_recursive(p)
    for i in [0, 5, 16]:
        loo = poibin_pmf_loo(f, p[i])
        rest = poibin_pmf_recursive(jnp.delete(p, i))
        np.testing.assert_allclose(np.asarray(loo[:-1]), np.asarray(rest),
                                   atol=1e-12)
        np.testing.assert_allclose(np.asarray(poibin_convolve(loo, p[i])),
                                   np.asarray(f), atol=1e-12)


def test_loo_deconvolution_corners():
    p = jnp.asarray([0.0, 1.0, 0.5, 0.25])
    f = poibin_pmf_recursive(p)
    for i in range(4):
        loo = poibin_pmf_loo(f, p[i])
        rest = poibin_pmf_recursive(jnp.delete(p, i))
        np.testing.assert_allclose(np.asarray(loo[:-1]), np.asarray(rest),
                                   atol=1e-14)
