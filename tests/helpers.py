"""Shared test helpers: Nash-equilibrium certification.

Several test files used to carry their own ad-hoc copy of the same check —
"no unilateral deviation on a grid is profitable". This module is the one
implementation, for both game flavors:

* :func:`max_symmetric_deviation` — symmetric game (everyone at p*): the
  best profitable deviation of one node over an action grid, via the O(N)
  Binomial decomposition in ``symmetric_player_utility``.
* :func:`max_heterogeneous_deviation` — heterogeneous profile: delegates to
  the jitted vectorized certifier in :mod:`repro.core.asymmetric_batched`.

Both return the *gain* of the best deviation (≤ tol certifies an NE); the
``assert_*`` wrappers fail with the offending numbers in the message.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.asymmetric_batched import verify_equilibrium_batched
from repro.core.duration import DurationModel
from repro.core.game import P_MIN
from repro.core.utility import UtilityParams, symmetric_player_utility

NE_TOL = 1e-4


def max_symmetric_deviation(
    p_star: float,
    params: UtilityParams,
    dur: DurationModel,
    grid: int = 256,
) -> float:
    """Max profitable unilateral deviation from the symmetric profile p*."""
    p_star = jnp.asarray(p_star)
    gridv = jnp.linspace(P_MIN, 1.0, grid)
    u_eq = symmetric_player_utility(p_star, p_star, params, dur)
    u_dev = jax.vmap(
        lambda q: symmetric_player_utility(q, p_star, params, dur))(gridv)
    return float(jnp.max(u_dev) - u_eq)


def max_heterogeneous_deviation(
    costs: jax.Array,
    gammas: jax.Array,
    dur: DurationModel,
    p: jax.Array,
    grid: int = 64,
) -> float:
    """Max profitable unilateral deviation from a heterogeneous profile.

    Single-game helper: the unpack below raises if a batch sneaks in
    (certifying only scenario 0 of a batch would be silently wrong).
    """
    (dev,) = verify_equilibrium_batched(costs, gammas, dur, jnp.asarray(p),
                                        grid=grid)
    return float(dev)


def assert_symmetric_ne(p_star, params, dur, tol: float = NE_TOL,
                        grid: int = 256) -> None:
    gain = max_symmetric_deviation(p_star, params, dur, grid=grid)
    assert gain <= tol, (
        f"profitable deviation {gain:.3e} > {tol:.1e} from symmetric "
        f"p*={float(p_star):.6f} (gamma={params.gamma}, c={params.cost})")


def assert_heterogeneous_ne(costs, gammas, dur, p, tol: float = NE_TOL,
                            grid: int = 64) -> None:
    gain = max_heterogeneous_deviation(costs, gammas, dur, p, grid=grid)
    assert gain <= tol, (
        f"profitable deviation {gain:.3e} > {tol:.1e} from profile "
        f"{[round(float(x), 4) for x in jnp.asarray(p)]}")


def direct_deviation(costs, gammas, d_tab, p, *, grid: int = 64):
    """``(B,)`` largest gain of a unilateral deviation to a grid point, in
    numpy: each node's opponents' pmf built directly as the product of
    their Bernoulli factors (no deconvolution), the gain of node i moving
    from p_i to q as ``(p_i - q)(slope_i + c_i) - gamma_i (log_aoi(q) -
    log_aoi(p_i))``, floored at 0."""
    costs, gammas, p = (np.asarray(x) for x in (costs, gammas, p))
    d_tab = np.asarray(d_tab)
    b, n = p.shape
    g = np.zeros((b, n, n))
    g[:, :, 0] = 1.0
    for j in range(n):
        pj = np.broadcast_to(p[:, j:j + 1], (b, n)).copy()
        pj[:, j] = 0.0                      # node j is not its own opponent
        g[:, :, 1:] = g[:, :, 1:] * (1.0 - pj[..., None]) \
            + g[:, :, :-1] * pj[..., None]
        g[:, :, 0] *= 1.0 - pj
    slope = g @ (d_tab[1:] - d_tab[:-1])   # E[D] rise per unit of p_i

    def log_aoi(x):
        return np.log(1.0 / x - 0.5)

    q = np.linspace(P_MIN, 1.0, grid)
    gain = ((p - q[:, None, None]) * (slope + costs)
            - gammas * (log_aoi(q)[:, None, None] - log_aoi(p)))
    return np.maximum(gain.max(axis=(0, 2)), 0.0)


def _best_response(a, gamma):
    """Maximiser over [P_MIN, 1] of ``a p - gamma log(1/p - 1/2)``: the
    root of ``p (2 - p) = -2 gamma / a`` when a < 0, else a corner."""
    with np.errstate(divide="ignore", invalid="ignore"):
        interior = 1.0 - np.sqrt(np.clip(1.0 + 2.0 * gamma / a, 0.0, 1.0))
    interior = np.clip(interior, P_MIN, 1.0)
    return np.where(gamma <= 0.0, np.where(a > 0.0, 1.0, P_MIN),
                    np.where(a >= 0.0, 1.0, interior))


def gauss_seidel_reference(costs, gammas, d_tab, *, damping: float = 0.5,
                           max_iters: int = 200, tol: float = 1e-5,
                           p0: float = 0.5):
    """Damped Gauss-Seidel best responses of a ``(B, N)`` batch, in numpy.

    Node i's opponents' pmf is built directly, as the product of the other
    nodes' Bernoulli factors, at every node step (no deconvolution, no
    transform). Sweeps stop per fleet when no node moved by ``tol``, as in
    :func:`repro.core.asymmetric_batched.solve_heterogeneous`. Returns
    ``(p, converged, iters)``.
    """
    costs, gammas = np.asarray(costs), np.asarray(gammas)
    d_tab = np.asarray(d_tab)
    b, n = costs.shape
    dd = d_tab[1:] - d_tab[:-1]
    p = np.full((b, n), p0)
    iters = np.zeros(b, np.int64)
    done = np.zeros(b, bool)
    for _ in range(max_iters):
        active = ~done
        delta = np.zeros(b)
        for i in range(n):
            g = np.zeros((b, n))
            g[:, 0] = 1.0
            for j in range(n):
                if j != i:
                    pj = p[:, j:j + 1]
                    g[:, 1:] = g[:, 1:] * (1.0 - pj) + g[:, :-1] * pj
                    g[:, 0] *= 1.0 - pj[:, 0]
            br = _best_response(-(g @ dd) - costs[:, i], gammas[:, i])
            new = (1.0 - damping) * p[:, i] + damping * br
            delta = np.maximum(delta, np.where(active,
                                               np.abs(new - p[:, i]), 0.0))
            p[:, i] = np.where(active, new, p[:, i])
        iters += active
        done |= active & (delta < tol)
        if done.all():
            break
    return p, done, iters
