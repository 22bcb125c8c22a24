"""Plain reference of the heterogeneous participation game, in numpy.

Player i joins a round with probability p_i. Its utility (arXiv:2503.21722
eqs. 9-11) is ``-E[d(K)] - gamma_i log(1/p_i - 1/2) - c_i p_i``, with K
the number of participants (a Poisson-binomial count) and d(k) the rounds
a task takes with k participants. A profile is an equilibrium when no
player gains by a unilateral deviation; the certificate is the largest
gain over a grid of deviations, floored at 0.

Everything here is computed directly: the others' count distribution of
each player by the product of their Bernoulli factors (no deconvolution),
the duration table by a least-squares fit of the analytic surrogate
``d(k) = d_inf + slope N / k``. ``dtype`` is float64 for the reference and
float32 for the control. Imports nothing of the system under test.
"""
from __future__ import annotations

import numpy as np


def duration_table(n: int, d: dict, dtype=np.float64) -> np.ndarray:
    """d(0..n): a degree-``d['degree']`` polynomial in x = k/n fitted to
    ``min(d_inf + slope n / k, horizon)`` at k = 1..n, held at its value at
    ``hi_frac`` plus a quadratic rise of ``rise`` rounds above it, clipped
    to [min over k of the surrogate, horizon], and the horizon at k = 0."""
    k = np.arange(1, n + 1, dtype=np.float64)
    surrogate = d["d_inf"] + d["slope"] * n / k
    x = k / n
    vander = np.vander(x, d["degree"] + 1, increasing=True)
    coeffs, *_ = np.linalg.lstsq(vander, np.minimum(surrogate, d["horizon"]),
                                 rcond=None)
    hi = d["hi_frac"]

    def poly(v):
        return np.vander(np.atleast_1d(v), d["degree"] + 1,
                         increasing=True) @ coeffs

    xs = np.arange(n + 1, dtype=np.float64) / n
    table = np.where(xs > hi,
                     poly(hi)[0] + d["rise"] * ((xs - hi) / (1 - hi)) ** 2,
                     poly(np.clip(xs, 0.0, hi)))
    table = np.clip(table, surrogate.min(), d["horizon"])
    table[0] = d["horizon"]
    return table.astype(dtype)


def others_pmf(p: np.ndarray) -> np.ndarray:
    """``(B, N, N)``: for each fleet and player i, the distribution of how
    many of the other N-1 players join (support 0..N-1)."""
    b, n = p.shape
    pmf = np.zeros((b, n, n), p.dtype)
    pmf[:, :, 0] = 1
    one = p.dtype.type(1)
    for j in range(n):
        pj = np.broadcast_to(p[:, j:j + 1], (b, n))[..., None].copy()
        pj[:, j] = 0  # player j is not among its own others
        shifted = np.concatenate(
            [np.zeros((b, n, 1), p.dtype), pmf[:, :, :-1]], axis=2)
        pmf = pmf * (one - pj) + shifted * pj
    return pmf


def _tables(p, d_tab):
    """Per player: E[d] without it (``base``) and the rise of E[d] per unit
    of its own probability (``slope``)."""
    g = others_pmf(p)
    base = g @ d_tab[:-1]
    slope = g @ (d_tab[1:] - d_tab[:-1])
    return base, slope


def log_aoi(q):
    one = q.dtype.type(1)
    return np.log(one / np.clip(q, q.dtype.type(1e-9), one)
                  - q.dtype.type(0.5))


def deviation(costs, gammas, d_tab, p, *, grid: int, p_min: float):
    """``(B,)`` largest gain of a unilateral deviation to a grid point."""
    dt = p.dtype.type
    base, slope = _tables(p, d_tab)
    q = np.linspace(p_min, 1.0, grid).astype(p.dtype)
    u_dev = (-(base[..., None] + q * slope[..., None])
             - gammas[..., None] * log_aoi(q) - costs[..., None] * q)
    u_eq = -(base + p * slope) - gammas * log_aoi(p) - costs * p
    return np.maximum((u_dev - u_eq[..., None]).max(axis=(1, 2)), dt(0))


def best_response(slope_u, cost, gamma, p_min):
    """Maximiser over [p_min, 1] of ``a p - gamma log(1/p - 1/2)``, a =
    slope_u - cost: the root of ``p (2 - p) = -2 gamma / a`` when a < 0."""
    dt = slope_u.dtype.type
    a = slope_u - cost
    with np.errstate(divide="ignore", invalid="ignore"):
        interior = dt(1) - np.sqrt(np.clip(dt(1) + dt(2) * gamma / a,
                                           dt(0), dt(1)))
    interior = np.clip(interior, dt(p_min), dt(1))
    return np.where(gamma <= 0, np.where(a > 0, dt(1), dt(p_min)),
                    np.where(a >= 0, dt(1), interior))


def solve(costs, gammas, d_tab, *, damping, max_iters, tol, p0, p_min):
    """Damped Gauss-Seidel best-response dynamics, player by player in
    order, until a sweep moves no player by ``tol`` or more."""
    dt = costs.dtype.type
    b, n = costs.shape
    p = np.full((b, n), dt(p0))
    dd = d_tab[1:] - d_tab[:-1]
    iters = np.zeros(b, np.int64)
    done = np.zeros(b, bool)
    for _ in range(max_iters):
        active = ~done
        delta = np.zeros(b, costs.dtype)
        for i in range(n):
            others = p.copy()
            others[:, i] = 0
            g = np.zeros((b, n), costs.dtype)
            g[:, 0] = 1
            for j in range(n):
                shifted = np.concatenate([np.zeros((b, 1), costs.dtype),
                                          g[:, :-1]], axis=1)
                g = g * (dt(1) - others[:, j:j + 1]) \
                    + shifted * others[:, j:j + 1]
            br = best_response(-(g @ dd), costs[:, i], gammas[:, i], p_min)
            new = (dt(1) - dt(damping)) * p[:, i] + dt(damping) * br
            delta = np.maximum(delta, np.where(active,
                                               np.abs(new - p[:, i]), 0))
            p[:, i] = np.where(active, new, p[:, i])
        iters += active
        done |= active & (delta < dt(tol))
        if done.all():
            break
    return p, done, iters
