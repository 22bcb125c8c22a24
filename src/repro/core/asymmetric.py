"""Beyond-paper extension: heterogeneous nodes and asymmetric equilibria.

The paper assumes identical nodes and solves the symmetric NE; its §V names
heterogeneous extensions as future work. Here nodes carry individual cost
factors ``c_i`` (e.g. battery-constrained sensors vs mains-powered gateways)
and optionally individual AoI weights ``gamma_i``. We compute:

* asymmetric best-response dynamics over the full Poisson-Binomial profile
  (the exact E[D] of eq. 8 with per-node probabilities — no mean-field
  approximation), damped to a fixed point;
* the utilitarian optimum over a common p (planner without price
  discrimination) and the heterogeneity-aware social cost of the reached
  profile, giving a heterogeneous PoA.

The heavy lifting lives in :mod:`repro.core.asymmetric_batched`: one jitted
XLA program runs the damped Gauss-Seidel sweep as a `lax.scan` over nodes
with an O(N) leave-one-out step on the fleet's characteristic function,
over a batch of scenarios. :func:`best_response_dynamics`,
:func:`verify_equilibrium`, and :func:`planner_coordinate_descent` below
keep their original signatures and semantics but delegate there (B = 1);
the pre-batching Python-loop implementations are retained as
``*_reference`` oracles for tests.

Everything reuses :mod:`repro.core.poibin`; the per-node best response
exploits the same decomposition as the symmetric case: with opponents'
profile fixed, u_i is linear in p_i (duration, cost) plus the concave AoI
term, so the BR is either a corner or the unique stationary point of the
concave part (closed form in
:func:`repro.core.asymmetric_batched.best_response_given_slope`).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.aoi import log_aoi
from repro.core.asymmetric_batched import (P_MIN, best_response_given_slope,
                                           planner_batched,
                                           solve_heterogeneous,
                                           verify_equilibrium_batched)
from repro.core.duration import DurationModel
from repro.core.poibin import poibin_pmf

__all__ = [
    "HeterogeneousGame",
    "best_response_dynamics",
    "best_response_dynamics_reference",
    "planner_coordinate_descent",
    "verify_equilibrium",
    "verify_equilibrium_reference",
]


@dataclasses.dataclass(frozen=True)
class HeterogeneousGame:
    """N nodes with per-node cost factors and incentive weights."""

    costs: jax.Array              # (N,) c_i
    gammas: jax.Array             # (N,) gamma_i
    dur: DurationModel

    @property
    def n(self) -> int:
        return int(self.costs.shape[0])

    def duration_slope(self, p: jax.Array, i: int) -> jax.Array:
        """E[d(m_-i + 1)] - E[d(m_-i)]: node i's marginal effect on E[D]."""
        p_others = jnp.delete(p, i, assume_unique_indices=True)
        pmf = poibin_pmf(p_others)                    # (N,) over 0..N-1
        tab = self.dur.table()                        # (N+1,)
        return jnp.sum(pmf * (tab[1:] - tab[:-1]))

    def utility(self, p: jax.Array, i: int) -> jax.Array:
        pmf = poibin_pmf(p)
        e_d = jnp.sum(pmf * self.dur.table())
        return (-e_d - self.gammas[i] * log_aoi(p[i])
                - self.costs[i] * p[i])

    def best_response(self, p: jax.Array, i: int) -> jax.Array:
        """Exact BR of node i: corner or stationary point of the concave part.

        Closed form shared with the batched engine — see
        :func:`repro.core.asymmetric_batched.best_response_given_slope` for
        the derivation (and the two-sided division guard at a = 0).
        """
        slope = -self.duration_slope(p, i)            # utility slope part
        return best_response_given_slope(slope, self.costs[i], self.gammas[i])

    def social_cost(self, p: jax.Array) -> jax.Array:
        """Sum over nodes of (E[D] + c_i p_i) (transfers excluded)."""
        pmf = poibin_pmf(p)
        e_d = jnp.sum(pmf * self.dur.table())
        return self.n * e_d + jnp.sum(self.costs * p)


def best_response_dynamics(
    game: HeterogeneousGame,
    p0: jax.Array | None = None,
    damping: float = 0.5,
    max_iters: int = 200,
    tol: float = 1e-5,
) -> tuple[jax.Array, bool, int]:
    """Damped Gauss-Seidel (sequential round-robin) best-response iteration.

    Sequential updates avoid the simultaneous-update cycling that strongly
    coupled congestion-style games exhibit. Returns (profile, converged,
    iters); the fixed point is an asymmetric NE (each node's BR given the
    others).

    Delegates to the batched engine (B = 1 of one jitted XLA program) with
    identical semantics; see :func:`best_response_dynamics_reference` for the
    pre-batching Python loop it is tested against.
    """
    sol = solve_heterogeneous(game.costs, game.gammas, game.dur, p0=p0,
                              damping=damping, max_iters=max_iters, tol=tol)
    return sol.single()


def best_response_dynamics_reference(
    game: HeterogeneousGame,
    p0: jax.Array | None = None,
    damping: float = 0.5,
    max_iters: int = 200,
    tol: float = 1e-5,
) -> tuple[jax.Array, bool, int]:
    """The original eager Gauss-Seidel loop (oracle for the batched engine)."""
    p = jnp.full((game.n,), 0.5) if p0 is None else jnp.asarray(p0)
    for it in range(max_iters):
        delta = 0.0
        for i in range(game.n):
            br = game.best_response(p, i)
            new_pi = (1 - damping) * p[i] + damping * br
            delta = max(delta, float(jnp.abs(new_pi - p[i])))
            p = p.at[i].set(new_pi)
        if delta < tol:
            return p, True, it + 1
    return p, False, max_iters


def planner_coordinate_descent(
    game: HeterogeneousGame,
    p0: jax.Array,
    grid: int = 101,
    rounds: int = 20,
) -> jax.Array:
    """Heterogeneity-aware planner: round-robin per-node minimization of the
    social cost. Monotone non-increasing, so started from any profile it
    lower-bounds that profile's cost — the PoA denominator for heterogeneous
    games (a common-p planner is provably suboptimal under cost spread).

    Delegates to the jitted :func:`repro.core.asymmetric_batched.planner_batched`.
    The social cost is linear in each ``p_i`` with the others fixed, so each
    coordinate minimum is a corner and the historical ``grid`` parameter is
    moot (kept for API compatibility — a grid argmin of a linear function
    picks the same corner).
    """
    del grid  # exact corner selection supersedes the grid argmin
    return planner_batched(game.costs, game.dur, jnp.asarray(p0),
                           rounds=rounds)[0]


def verify_equilibrium(game: HeterogeneousGame, p: jax.Array,
                       grid: int = 64) -> float:
    """Max profitable unilateral deviation over a grid (0 at an exact NE).

    Delegates to the jitted vectorized deviation grid in
    :func:`repro.core.asymmetric_batched.verify_equilibrium_batched`.
    """
    return float(verify_equilibrium_batched(game.costs, game.gammas, game.dur,
                                            jnp.asarray(p), grid=grid)[0])


def verify_equilibrium_reference(game: HeterogeneousGame, p: jax.Array,
                                 grid: int = 64) -> float:
    """The original Python double loop (oracle for the jitted certifier)."""
    worst = 0.0
    gridv = jnp.linspace(P_MIN, 1.0, grid)
    for i in range(game.n):
        u_eq = float(game.utility(p, i))
        for q in gridv:
            u_dev = float(game.utility(p.at[i].set(q), i))
            worst = max(worst, u_dev - u_eq)
    return worst
