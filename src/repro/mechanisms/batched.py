"""Batched symmetric-NE + centralized-optimum solver (one XLA program).

Mechanism design needs the game solved *thousands* of times — a γ-grid for
AoI calibration, an r-grid for Stackelberg pricing, (γ, c, N) scenario
sweeps. The scalar solver in :mod:`repro.core.game` runs Python-level
bisection with eager JAX scalars (~100 dispatches per root); here the whole
pipeline is fixed-shape `lax` control flow, jitted once and batched over B
scenarios:

1. the symmetric marginal φ(p) = ∂u_i/∂p_i|_{p_i=p_-i=p} is evaluated in
   closed form on a shared action grid (the Binomial(N-1, p) opponent pmf and
   the duration table are the only ingredients — no Poisson-Binomial DFT, no
   autodiff);
2. interior equilibria are sign changes of φ refined by a fixed-iteration
   vectorized bisection; corner equilibria keep the scalar solver's
   semantics (p = P_MIN is an NE iff φ(P_MIN) ≤ 0, p = P_MAX iff φ(P_MAX) ≥ 0);
3. the centralized optimum is a grid argmin of the social cost E[D] + c·p
   refined by a fixed-iteration vectorized golden section.

Everything is (B,)- or (B, K)-shaped with NaN/mask padding so the program
has static shapes; `repro.core.game.solve_game` delegates here with B = 1.

Derivation of φ (see ``symmetric_player_utility``): with the other N-1 nodes
at p, E[D] is *linear* in p_i, slope Δe(p) = E[d(m+1)] - E[d(m)] with
m ~ Binomial(N-1, p); the AoI term -γ·log(1/p_i - 1/2) has derivative
-γ·(-2/(p_i(2-p_i))); the cost term contributes -c.  Hence

    φ(p) = -Δe(p) + 2γ / (p(2-p)) - c.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import xlog1py, xlogy

from repro.core.duration import DurationModel
from repro.core.game import P_MAX, P_MIN
from repro.core.utility import UtilityParams

__all__ = [
    "BatchedGameSolution",
    "binom_pmf",
    "batched_phi",
    "solve_batched",
    "solve_scenarios",
]

_NE_CAP = 1e6       # PoA cap, matches repro.core.game.price_of_anarchy
_DEDUP_TOL = 1e-4   # root-merging tolerance, matches solve_symmetric_ne


def binom_pmf(p: jax.Array, n: int) -> jax.Array:
    """Binomial(n, p) pmf over k = 0..n, batched over leading dims of ``p``.

    Stable at the p = 0 / p = 1 corners via xlogy/xlog1py (0·log 0 = 0).
    Shape: ``p (...,) -> (..., n+1)``.
    """
    k = jnp.arange(n + 1, dtype=p.dtype)
    log_comb = jnp.asarray(_log_comb(n), p.dtype)
    log_pmf = log_comb + xlogy(k, p[..., None]) + xlog1py(n - k, -p[..., None])
    return jnp.exp(log_pmf)


@functools.lru_cache(maxsize=None)
def _log_comb(n: int) -> np.ndarray:
    """``log C(n, k)`` for k = 0..n, on the host: ``n`` is static, and an
    f64 ``gammaln`` in the program costs ~18 s of TPU compile per use."""
    out = np.array([math.log(math.comb(n, k)) for k in range(n + 1)])
    out.setflags(write=False)       # cached: shared by every caller
    return out


def batched_phi(
    p: jax.Array,
    gammas: jax.Array,
    costs: jax.Array,
    d_tab: jax.Array,
) -> jax.Array:
    """φ(p) for a (B, ...) batch of symmetric profiles.

    Args:
        p: ``(B, M)`` evaluation points (or ``(B,)``).
        gammas / costs: ``(B,)`` scenario parameters.
        d_tab: ``(N+1,)`` duration table d(k).
    """
    n = d_tab.shape[0] - 1
    squeeze = p.ndim == 1
    if squeeze:
        p = p[:, None]
    pmf_others = binom_pmf(p, n - 1)                      # (B, M, N)
    delta_e = pmf_others @ (d_tab[1:] - d_tab[:-1])       # (B, M)
    phi = (-delta_e + 2.0 * gammas[:, None] / (p * (2.0 - p))
           - costs[:, None])
    return phi[:, 0] if squeeze else phi


@dataclasses.dataclass(frozen=True)
class BatchedGameSolution:
    """Fixed-shape solution of B simultaneous games.

    ``equilibria``/``ne_costs`` are ``(B, K)`` NaN-padded ascending arrays
    (slot 0 = the P_MIN corner, last slot = the P_MAX corner, interior roots
    between); ``ne_mask`` marks valid slots. Costs are the social cost
    E[D] + c·p of eq. (13) — worst/best NE and PoA are precomputed.
    """

    gammas: jax.Array      # (B,)
    costs: jax.Array       # (B,)
    equilibria: jax.Array  # (B, K) NaN-padded
    ne_mask: jax.Array     # (B, K) bool
    ne_costs: jax.Array    # (B, K) NaN-padded
    worst_ne: jax.Array    # (B,) argmax-cost NE (NaN when no NE)
    best_ne: jax.Array     # (B,) argmin-cost NE
    worst_ne_cost: jax.Array  # (B,)
    best_ne_cost: jax.Array   # (B,)
    opt_p: jax.Array       # (B,)
    opt_cost: jax.Array    # (B,)
    poa: jax.Array         # (B,) inf when no NE

    @property
    def batch(self) -> int:
        return int(self.poa.shape[0])

    def equilibria_list(self, i: int) -> list[float]:
        mask = np.asarray(self.ne_mask[i])
        return [float(x) for x in np.asarray(self.equilibria[i])[mask]]

    def ne_costs_list(self, i: int) -> list[float]:
        mask = np.asarray(self.ne_mask[i])
        return [float(x) for x in np.asarray(self.ne_costs[i])[mask]]


@functools.partial(
    jax.jit,
    static_argnames=("ne_grid", "opt_grid", "max_roots", "bisect_iters",
                     "golden_iters"))
def _solve_batched(
    gammas: jax.Array,
    costs: jax.Array,
    d_tab: jax.Array,
    *,
    ne_grid: int,
    opt_grid: int,
    max_roots: int,
    bisect_iters: int,
    golden_iters: int,
) -> dict[str, jax.Array]:
    n = d_tab.shape[0] - 1
    batch = gammas.shape[0]

    # ---- equilibria: φ on the grid, corners, vectorized bisection ----------
    grid = jnp.linspace(P_MIN, P_MAX, ne_grid)
    # Δe(p) is scenario-independent: share it across the batch.
    delta_e_grid = binom_pmf(grid, n - 1) @ (d_tab[1:] - d_tab[:-1])  # (G,)
    aoi_grid = 2.0 / (grid * (2.0 - grid))                            # (G,)
    phi_grid = (-delta_e_grid[None, :] + gammas[:, None] * aoi_grid[None, :]
                - costs[:, None])                                     # (B, G)

    corner_lo = phi_grid[:, 0] <= 0.0
    corner_hi = phi_grid[:, -1] >= 0.0

    sign = jnp.sign(phi_grid)
    crossing = sign[:, :-1] * sign[:, 1:] < 0.0                       # (B, G-1)
    cell = jnp.arange(ne_grid - 1)
    # First `max_roots` crossing cells per scenario; sentinel = ne_grid.
    cand = jnp.where(crossing, cell[None, :], ne_grid)
    cand = jnp.sort(cand, axis=1)[:, :max_roots]                      # (B, K)
    root_valid = cand < ne_grid
    cell_idx = jnp.minimum(cand, ne_grid - 2)
    lo = grid[cell_idx]
    hi = grid[cell_idx + 1]
    f_lo = jnp.take_along_axis(phi_grid, cell_idx, axis=1)

    def bisect_body(_, carry):
        lo, hi, f_lo = carry
        mid = 0.5 * (lo + hi)
        f_mid = batched_phi(mid, gammas, costs, d_tab)
        same_side = (f_mid > 0.0) == (f_lo > 0.0)
        return (jnp.where(same_side, mid, lo),
                jnp.where(same_side, hi, mid),
                jnp.where(same_side, f_mid, f_lo))

    lo, hi, _ = jax.lax.fori_loop(0, bisect_iters, bisect_body,
                                  (lo, hi, f_lo))
    roots = 0.5 * (lo + hi)                                           # (B, K)

    # Corner-NE dedup (scalar solver registers corners first, then skips any
    # interior root within _DEDUP_TOL of an already-found equilibrium).
    root_valid = root_valid & ~(
        corner_lo[:, None] & (jnp.abs(roots - grid[0]) < _DEDUP_TOL))
    root_valid = root_valid & ~(
        corner_hi[:, None] & (jnp.abs(roots - grid[-1]) < _DEDUP_TOL))
    for j in range(1, max_roots):
        for i in range(j):
            dup = (root_valid[:, i]
                   & (jnp.abs(roots[:, j] - roots[:, i]) < _DEDUP_TOL))
            root_valid = root_valid.at[:, j].set(root_valid[:, j] & ~dup)

    # Assemble ascending [P_MIN corner, interior roots..., P_MAX corner].
    eq = jnp.concatenate([
        jnp.full((batch, 1), grid[0]), roots, jnp.full((batch, 1), grid[-1]),
    ], axis=1)                                                        # (B, K+2)
    mask = jnp.concatenate([
        corner_lo[:, None], root_valid, corner_hi[:, None]], axis=1)

    # ---- social costs at the equilibria ------------------------------------
    e_d_at = binom_pmf(eq, n) @ d_tab                                  # (B, K+2)
    ne_cost = e_d_at + costs[:, None] * eq
    any_ne = jnp.any(mask, axis=1)
    worst_i = jnp.argmax(jnp.where(mask, ne_cost, -jnp.inf), axis=1)
    best_i = jnp.argmin(jnp.where(mask, ne_cost, jnp.inf), axis=1)

    # ---- centralized optimum: grid argmin + golden section -----------------
    g2 = jnp.linspace(P_MIN, P_MAX, opt_grid)
    e_d_grid = binom_pmf(g2, n) @ d_tab                                # (G2,)
    cost_grid = e_d_grid[None, :] + costs[:, None] * g2[None, :]       # (B, G2)
    i_min = jnp.argmin(cost_grid, axis=1)
    a = g2[jnp.maximum(i_min - 1, 0)]
    b = g2[jnp.minimum(i_min + 1, opt_grid - 1)]

    def social(p):  # (B,) social cost E[D] + c p
        return binom_pmf(p, n) @ d_tab + costs * p

    invphi = (np.sqrt(5.0) - 1.0) / 2.0
    c_ = b - invphi * (b - a)
    d_ = a + invphi * (b - a)
    f_c, f_d = social(c_), social(d_)

    def golden_body(_, carry):
        a, b, c_, d_, f_c, f_d = carry
        shrink_right = f_c < f_d          # minimum in [a, d]
        a2 = jnp.where(shrink_right, a, c_)
        b2 = jnp.where(shrink_right, d_, b)
        c2 = jnp.where(shrink_right, b2 - invphi * (b2 - a2), d_)
        d2 = jnp.where(shrink_right, c_, a2 + invphi * (b2 - a2))
        probe = jnp.where(shrink_right, c2, d2)
        f_probe = social(probe)
        f_c2 = jnp.where(shrink_right, f_probe, f_d)
        f_d2 = jnp.where(shrink_right, f_c, f_probe)
        return a2, b2, c2, d2, f_c2, f_d2

    a, b, *_ = jax.lax.fori_loop(0, golden_iters, golden_body,
                                 (a, b, c_, d_, f_c, f_d))
    opt_p = 0.5 * (a + b)
    opt_cost = social(opt_p)

    # ---- PoA (eq. 13) -------------------------------------------------------
    worst_cost = jnp.max(jnp.where(mask, ne_cost, -jnp.inf), axis=1)
    best_cost = jnp.min(jnp.where(mask, ne_cost, jnp.inf), axis=1)
    poa = jnp.minimum(worst_cost / jnp.maximum(opt_cost, 1e-12), _NE_CAP)
    poa = jnp.where(any_ne, poa, jnp.inf)

    nan = jnp.nan
    take = lambda arr, idx: jnp.take_along_axis(arr, idx[:, None], 1)[:, 0]
    return {
        "equilibria": jnp.where(mask, eq, nan),
        "ne_mask": mask,
        "ne_costs": jnp.where(mask, ne_cost, nan),
        "worst_ne": jnp.where(any_ne, take(eq, worst_i), nan),
        "best_ne": jnp.where(any_ne, take(eq, best_i), nan),
        "worst_ne_cost": jnp.where(any_ne, worst_cost, nan),
        "best_ne_cost": jnp.where(any_ne, best_cost, nan),
        "opt_p": opt_p,
        "opt_cost": opt_cost,
        "poa": poa,
    }


def solve_batched(
    gammas: jax.Array,
    costs: jax.Array,
    dur: DurationModel | jax.Array,
    *,
    ne_grid: int = 400,
    opt_grid: int = 2000,
    max_roots: int = 4,
    bisect_iters: int = 60,
    golden_iters: int = 40,
) -> BatchedGameSolution:
    """Solve B scenarios (γ_b, c_b) sharing one duration model, in one jit.

    Args:
        gammas / costs: ``(B,)`` UtilityParams weights per scenario.
        dur: a :class:`DurationModel` or a raw ``(N+1,)`` duration table.
        ne_grid / opt_grid: φ-grid and social-cost-grid resolutions (match
            ``solve_game``'s scalar defaults).
        max_roots: interior-equilibrium slots per scenario (K+2 total with
            corners); extra sign changes beyond this are dropped.
    """
    d_tab = dur.table() if isinstance(dur, DurationModel) else jnp.asarray(dur)
    gammas = jnp.atleast_1d(jnp.asarray(gammas, d_tab.dtype))
    costs = jnp.atleast_1d(jnp.asarray(costs, d_tab.dtype))
    if gammas.shape != costs.shape:
        raise ValueError(f"gammas {gammas.shape} vs costs {costs.shape}")
    out = _solve_batched(gammas, costs, d_tab, ne_grid=ne_grid,
                         opt_grid=opt_grid, max_roots=max_roots,
                         bisect_iters=bisect_iters, golden_iters=golden_iters)
    return BatchedGameSolution(gammas=gammas, costs=costs, **out)


def solve_scenarios(
    scenarios: list[UtilityParams],
    dur_for_n: dict[int, DurationModel],
    **solver_kwargs,
) -> list[BatchedGameSolution]:
    """(γ, c, N) sweep: group scenarios by N (shapes are static per N) and
    run one batched solve per group.

    Returns one :class:`BatchedGameSolution` per distinct N, in ascending-N
    order; each carries its scenarios in the original relative order.
    """
    by_n: dict[int, list[UtilityParams]] = {}
    for s in scenarios:
        by_n.setdefault(s.n_nodes, []).append(s)
    out = []
    for n in sorted(by_n):
        group = by_n[n]
        out.append(solve_batched(
            jnp.asarray([s.gamma for s in group]),
            jnp.asarray([s.cost for s in group]),
            dur_for_n[n], **solver_kwargs))
    return out
