"""Coalition-formation equilibria: batched hedonic partition dynamics.

The next game class after per-node participation (the asymmetric layer):
nodes choose *which coalition* — a pooled FedAvg group training its own
model — to join, in the spirit of participant-centric coalition formation
(Huang et al., arXiv:2207.12030) and free-riding under heterogeneous-agent
pooling (Yi et al., arXiv:2503.09039). The two-level game:

* **Inner game** — within a coalition ``S``, members play the existing
  heterogeneous participation game (utility
  ``u_i = -E[D_S] - γ_i·log E[Δ_i] - c_i·p_i``, eqs. 8-11 restricted to
  ``S``); its equilibrium is the certified asymmetric NE of
  :mod:`repro.core.asymmetric_batched`, solved here by the *same* damped
  Gauss-Seidel sweep run masked (``_gs_fixed_point(..., member=)``):
  non-members are pinned at ``p = 0`` exactly, whose Bernoulli factor is
  exactly 1 at every frequency, so an all-true mask reproduces
  :func:`~repro.core.asymmetric_batched.solve_heterogeneous` bitwise (the
  grand-coalition reduction pinned in
  ``tests/test_property_coalition.py``).
* **Outer game** — a hedonic partition game: node ``i`` in coalition
  ``S_c`` values a switch to ``S_{c'}`` at the utility it would earn at
  the *re-solved* inner NE of ``S_{c'} ∪ {i}`` (preferences depend only on
  the coalition joined — a hedonic game). :func:`solve_partition` runs
  jitted best-switch dynamics: per iteration every (node, coalition)
  candidate NE is solved in one batched program, the single most
  profitable eligible switch (respecting the per-coalition cap) is
  applied, and the dynamics stop when no node gains more than
  ``switch_tol`` — a partition (Nash-stable hedonic) equilibrium.

Certification and benchmarking mirror the asymmetric layer's surfaces:
:func:`verify_partition_batched` re-derives every switch gain *and* every
within-coalition deviation grid at the returned partition (0 at an exact
partition equilibrium), :func:`partition_planner_batched` descends the
per-coalition social cost from the equilibrium profile (corner descent —
the cost is linear in each ``p_i``), and :func:`partition_poa_report`
packages NE + certification + planner + PoA for a scenario batch.
Everything is written single-scenario and ``vmap``-ed over
(costs, gammas, cap) batches in the jitted wrappers.

Oracle-first rails: :func:`partition_equilibrium_reference` restates both
levels as plain Python loops over *compact* subgames (no masks — each
coalition's pmf is built from its members only), kept as the test oracle
for ``tests/test_property_coalition.py``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.aoi import log_aoi
from repro.core.asymmetric_batched import (P_MIN, _gs_fixed_point,
                                           best_response_given_slope)
from repro.core.duration import DurationModel
from repro.core.poibin import (poibin_convolve, poibin_pmf_loo,
                               poibin_pmf_recursive)

__all__ = [
    "PartitionSolution",
    "PartitionPoA",
    "solve_partition",
    "verify_partition_batched",
    "partition_social_cost_batched",
    "partition_planner_batched",
    "partition_poa_report",
    "partition_equilibrium_reference",
]


# ---------------------------------------------------------------------------
# inner game: Gauss-Seidel NE of one coalition at full fleet width, the
# heterogeneous engine's sweep with a member mask (``_gs_fixed_point``)
# ---------------------------------------------------------------------------

def _member_matrix(assign, m):
    """(M, N) bool coalition-membership masks from an (N,) assignment."""
    return jnp.arange(m)[:, None] == assign[None, :]


def _solve_masked(costs, gammas, d_tab, masks, *, damping, max_iters, tol):
    """Inner NE of each subgame in ``masks`` (``(K, N)`` bool) at width N,
    in one batch: ``(K, N)`` profiles (zeros off each subgame) and ``(K,)``
    convergence flags."""
    k = masks.shape[0]
    p0 = jnp.where(masks, 0.5, 0.0).astype(d_tab.dtype)
    p, conv, _ = _gs_fixed_point(
        jnp.broadcast_to(costs, masks.shape),
        jnp.broadcast_to(gammas, masks.shape),
        jnp.broadcast_to(d_tab, (k,) + d_tab.shape), p0, damping=damping,
        max_iters=max_iters, tol=tol, member=masks)
    return p, conv


def _solve_coalitions(costs, gammas, d_tab, member, *, damping, max_iters,
                      tol):
    """Inner NE of every coalition: (M, N) profiles (zeros off-coalition),
    (M,) convergence flags, and (M,) expected durations E[D_{S_c}]."""
    p_cs, conv = _solve_masked(costs, gammas, d_tab, member, damping=damping,
                               max_iters=max_iters, tol=tol)
    e_d = jax.vmap(poibin_pmf_recursive)(p_cs) @ d_tab
    return p_cs, conv, e_d


def _candidate_gains(costs, gammas, d_tab, assign, cap, *, m, damping,
                     max_iters, tol):
    """The hedonic deviation table of one scenario.

    Returns ``(gain, p_full, e_d, inner_conv)``: ``gain[i, c]`` is node
    i's utility change from joining coalition ``c`` (the re-solved NE of
    ``S_c ∪ {i}`` versus its current coalition's NE), ``-inf`` where the
    switch is ineligible (own coalition, or ``|S_c| ≥ cap``); ``p_full``
    the (N,) equilibrium profile of the current partition; ``e_d`` the
    (M,) per-coalition expected durations; ``inner_conv`` whether every
    inner solve (current and candidate) converged.
    """
    n = costs.shape[0]
    member = _member_matrix(assign, m)
    p_cs, conv, e_d = _solve_coalitions(costs, gammas, d_tab, member,
                                        damping=damping,
                                        max_iters=max_iters, tol=tol)
    p_full = jnp.sum(p_cs, axis=0)              # coalitions are disjoint
    u_cur = (-e_d[assign] - gammas * log_aoi(p_full) - costs * p_full)

    # candidate masks: node i joins coalition c → S_c ∪ {i}, (N, M, N)
    cand = member[None, :, :] | jnp.eye(n, dtype=bool)[:, None, :]
    p_cand, conv_cand = _solve_masked(
        costs, gammas, d_tab, cand.reshape(n * m, n), damping=damping,
        max_iters=max_iters, tol=tol)
    p_cand, conv_cand = p_cand.reshape(n, m, n), conv_cand.reshape(n, m)
    e_d_cand = jax.vmap(jax.vmap(poibin_pmf_recursive))(p_cand) @ d_tab
    p_i_cand = p_cand[jnp.arange(n), :, jnp.arange(n)]          # (N, M)
    u_cand = (-e_d_cand - gammas[:, None] * log_aoi(p_i_cand)
              - costs[:, None] * p_i_cand)
    sizes = jnp.sum(member, axis=1)
    eligible = ((assign[:, None] != jnp.arange(m)[None, :])
                & (sizes[None, :] < cap))
    gain = jnp.where(eligible, u_cand - u_cur[:, None], -jnp.inf)
    return gain, p_full, e_d, conv.all() & conv_cand.all()


def _partition_dynamics_one(costs, gammas, d_tab, cap, assign0, *, m,
                            damping, max_iters, tol, switch_tol,
                            max_switches):
    """Best-switch hedonic dynamics of one scenario (while_loop)."""
    gains = functools.partial(_candidate_gains, costs, gammas, d_tab, m=m,
                              damping=damping, max_iters=max_iters, tol=tol)

    def cond(state):
        _, best, applied = state
        return (best > switch_tol) & (applied < max_switches)

    def body(state):
        assign, _, applied = state
        gain, _, _, _ = gains(assign, cap)
        flat = jnp.argmax(gain)
        i, c = flat // m, flat % m
        best = gain.reshape(-1)[flat]
        improving = best > switch_tol
        new_assign = jnp.where(improving,
                               assign.at[i].set(c.astype(assign.dtype)),
                               assign)
        return new_assign, best, applied + jnp.asarray(improving, jnp.int32)

    assign, _, switches = jax.lax.while_loop(
        cond, body,
        (assign0, jnp.asarray(jnp.inf, d_tab.dtype),
         jnp.asarray(0, jnp.int32)))
    # one last gain evaluation at the settled partition: the certificate
    # (and the equilibrium profile/durations) of what is returned
    gain, p_full, e_d, inner_conv = gains(assign, cap)
    best = jnp.maximum(jnp.max(gain), 0.0)      # -inf → 0 when no switch
    converged = best <= switch_tol
    return assign, p_full, e_d, converged, switches, best, inner_conv


@functools.partial(jax.jit, static_argnames=(
    "m", "damping", "max_iters", "tol", "switch_tol", "max_switches"))
def _solve_partition_vmapped(costs, gammas, d_tab, cap, assign0, *, m,
                             damping, max_iters, tol, switch_tol,
                             max_switches):
    fn = functools.partial(_partition_dynamics_one, m=m, damping=damping,
                           max_iters=max_iters, tol=tol,
                           switch_tol=switch_tol, max_switches=max_switches)
    return jax.vmap(fn)(costs, gammas, d_tab, cap, assign0)


@dataclasses.dataclass(frozen=True)
class PartitionSolution:
    """A vmapped batch of partition-equilibrium solves."""

    costs: jax.Array        # (B, N)
    gammas: jax.Array       # (B, N)
    assign: jax.Array       # (B, N) coalition index per node, in [0, M)
    p: jax.Array            # (B, N) inner-NE participation profiles
    e_d: jax.Array          # (B, M) per-coalition E[D_{S_c}]
    converged: jax.Array    # (B,) hedonic dynamics reached stability
    inner_converged: jax.Array  # (B,) every inner GS solve converged
    switches: jax.Array     # (B,) coalition switches applied
    max_gain: jax.Array     # (B,) best remaining switch gain (≤ switch_tol
    #                             wherever ``converged``)
    n_coalitions: int

    @property
    def batch(self) -> int:
        return int(self.assign.shape[0])

    @property
    def sizes(self) -> jax.Array:
        """(B, M) coalition sizes."""
        return jnp.sum(
            self.assign[:, None, :] == jnp.arange(self.n_coalitions)[
                None, :, None], axis=-1)


def _prepare_partition_batch(costs, gammas, dur, n_coalitions, cap, assign0):
    from repro.core.asymmetric_batched import _prepare_batch

    costs, gammas, d_tab, _ = _prepare_batch(costs, gammas, dur, None)
    b, n = costs.shape
    m = int(n_coalitions)
    if m < 1:
        raise ValueError(f"n_coalitions must be >= 1, got {m}")
    cap = jnp.asarray(n if cap is None else cap, jnp.int32)
    cap = jnp.broadcast_to(jnp.atleast_1d(cap), (b,))
    if assign0 is None:
        assign0 = jnp.arange(n, dtype=jnp.int32) % m     # round-robin
    assign0 = jnp.broadcast_to(
        jnp.atleast_2d(jnp.asarray(assign0, jnp.int32)), (b, n))
    return costs, gammas, d_tab, cap, assign0, b, n, m


def solve_partition(
    costs: jax.Array,
    gammas: jax.Array,
    dur: DurationModel | jax.Array,
    *,
    n_coalitions: int,
    cap: jax.Array | int | None = None,
    assign0: jax.Array | None = None,
    damping: float = 0.5,
    max_iters: int = 200,
    tol: float = 1e-5,
    switch_tol: float = 1e-6,
    max_switches: int | None = None,
) -> PartitionSolution:
    """Solve a batch of coalition-formation games in one jitted program.

    Args:
        costs / gammas: ``(N,)`` or ``(B, N)`` per-node game parameters
            (broadcast against each other like
            :func:`~repro.core.asymmetric_batched.solve_heterogeneous`).
        dur: shared :class:`DurationModel` / ``(N+1,)`` table or a
            per-scenario ``(B, N+1)`` stack — ``d(k)`` is indexed by the
            number of *participants inside one coalition*.
        n_coalitions: M, the number of coalition slots (static — it fixes
            program shapes). Empty coalitions are fine: a node can open
            one by switching in (subject to ``cap``).
        cap: max coalition size — scalar or per-scenario ``(B,)``
            (dynamic; it only gates switch eligibility). ``None`` = no cap.
        assign0: initial assignment, ``(N,)`` or ``(B, N)`` ints in
            ``[0, M)``; default round-robin ``i % M`` (the grand coalition
            when ``M == 1``).
        damping / max_iters / tol: inner Gauss-Seidel controls
            (:func:`~repro.core.asymmetric_batched.solve_heterogeneous`
            defaults and semantics).
        switch_tol: a partition is stable when no node's best eligible
            switch gains more than this (also the certification bar of
            :func:`verify_partition_batched`).
        max_switches: outer-iteration budget; default ``4·N·M``.

    Returns:
        A :class:`PartitionSolution`; ``converged`` marks scenarios whose
        dynamics reached a stable partition within budget.
    """
    costs, gammas, d_tab, cap, assign0, b, n, m = _prepare_partition_batch(
        costs, gammas, dur, n_coalitions, cap, assign0)
    if max_switches is None:
        max_switches = 4 * n * m
    assign, p, e_d, conv, switches, max_gain, inner = \
        _solve_partition_vmapped(
            costs, gammas, d_tab, cap, assign0, m=m,
            damping=float(damping), max_iters=int(max_iters),
            tol=float(tol), switch_tol=float(switch_tol),
            max_switches=int(max_switches))
    return PartitionSolution(costs=costs, gammas=gammas, assign=assign, p=p,
                             e_d=e_d, converged=conv, inner_converged=inner,
                             switches=switches, max_gain=max_gain,
                             n_coalitions=m)


# ---------------------------------------------------------------------------
# certification: switch gains + within-coalition deviation grid
# ---------------------------------------------------------------------------

def _verify_partition_one(costs, gammas, d_tab, assign, cap, p, *, m, grid,
                          damping, max_iters, tol):
    n = costs.shape[0]
    member = _member_matrix(assign, m)
    # within-coalition unilateral p-deviations on a grid: per coalition,
    # the same leave-one-out base/slope table as the asymmetric certifier,
    # gathered at each node's own coalition
    f_cs = jax.vmap(poibin_pmf_recursive)(p * member)          # (M, N+1)
    dd = d_tab[1:] - d_tab[:-1]
    loo = jax.vmap(jax.vmap(poibin_pmf_loo, in_axes=(None, 0)))(
        f_cs, jnp.broadcast_to(p, (m, n)))                     # (M, N, N+1)
    base = loo[:, :, :-1] @ d_tab[:-1]                         # (M, N)
    slope = loo[:, :, :-1] @ dd
    base_i = base[assign, jnp.arange(n)]                       # (N,)
    slope_i = slope[assign, jnp.arange(n)]
    gridv = jnp.linspace(P_MIN, 1.0, grid).astype(p.dtype)
    u_dev = (-(base_i[:, None] + gridv[None, :] * slope_i[:, None])
             - gammas[:, None] * log_aoi(gridv)[None, :]
             - costs[:, None] * gridv[None, :])                # (N, G)
    u_eq = (-(base_i + p * slope_i) - gammas * log_aoi(p) - costs * p)
    dev_p = jnp.max(u_dev - u_eq[:, None])
    # coalition-switch deviations: the dynamics' own gain table
    gain, _, _, _ = _candidate_gains(costs, gammas, d_tab, assign, cap, m=m,
                                     damping=damping, max_iters=max_iters,
                                     tol=tol)
    return jnp.maximum(jnp.maximum(dev_p, jnp.max(gain)), 0.0)


@functools.partial(jax.jit, static_argnames=(
    "m", "grid", "damping", "max_iters", "tol"))
def _verify_partition_vmapped(costs, gammas, d_tab, assign, cap, p, *, m,
                              grid, damping, max_iters, tol):
    fn = functools.partial(_verify_partition_one, m=m, grid=grid,
                           damping=damping, max_iters=max_iters, tol=tol)
    return jax.vmap(fn)(costs, gammas, d_tab, assign, cap, p)


def verify_partition_batched(
    costs: jax.Array,
    gammas: jax.Array,
    dur: DurationModel | jax.Array,
    assign: jax.Array,
    p: jax.Array,
    *,
    n_coalitions: int,
    cap: jax.Array | int | None = None,
    grid: int = 64,
    damping: float = 0.5,
    max_iters: int = 200,
    tol: float = 1e-5,
) -> jax.Array:
    """Max profitable deviation per scenario (0 at a partition equilibrium).

    Two deviation classes are certified in one jitted program: every
    node's *within-coalition* participation deviation over a ``grid``
    (the asymmetric certifier restricted to the node's coalition) and
    every node's *coalition switch* (the re-solved hedonic gain table of
    the dynamics, eligibility — own coalition, cap — included). Returns
    ``(B,)``; a returned partition of :func:`solve_partition` with
    ``converged`` true certifies ≤ its ``switch_tol`` by construction on
    the switch class, and ≤ the inner solver's residual on the grid class.
    """
    costs, gammas, d_tab, cap, assign, b, n, m = _prepare_partition_batch(
        costs, gammas, dur, n_coalitions, cap, assign)
    p = jnp.broadcast_to(jnp.atleast_2d(jnp.asarray(p, d_tab.dtype)), (b, n))
    return _verify_partition_vmapped(
        costs, gammas, d_tab, assign, cap, p, m=m, grid=int(grid),
        damping=float(damping), max_iters=int(max_iters), tol=float(tol))


# ---------------------------------------------------------------------------
# social cost, per-coalition planner, PoA report
# ---------------------------------------------------------------------------

def _partition_social_cost_one(costs, d_tab, assign, p, *, m):
    member = _member_matrix(assign, m)
    sizes = jnp.sum(member, axis=1)
    e_d = jax.vmap(poibin_pmf_recursive)(p * member) @ d_tab     # (M,)
    # empty coalitions contribute 0·d(0) — the d_zero horizon never leaks
    return jnp.sum(sizes * e_d) + costs @ p


@functools.partial(jax.jit, static_argnames=("m",))
def _partition_social_cost_vmapped(costs, d_tab, assign, p, *, m):
    return jax.vmap(functools.partial(_partition_social_cost_one, m=m))(
        costs, d_tab, assign, p)


def partition_social_cost_batched(
    costs: jax.Array,
    dur: DurationModel | jax.Array,
    assign: jax.Array,
    p: jax.Array,
    *,
    n_coalitions: int,
) -> jax.Array:
    """``Σ_c |S_c|·E[D_{S_c}] + Σ_i c_i p_i`` per scenario, ``(B,)``."""
    costs, _, d_tab, _, assign, b, n, m = _prepare_partition_batch(
        costs, jnp.zeros_like(jnp.asarray(costs, jnp.float64)), dur,
        n_coalitions, None, assign)
    p = jnp.broadcast_to(jnp.atleast_2d(jnp.asarray(p, d_tab.dtype)), (b, n))
    return _partition_social_cost_vmapped(costs, d_tab, assign, p, m=m)


def _partition_planner_one(costs, d_tab, assign, p0, *, m, rounds):
    """Per-coalition corner coordinate descent of the partition's social
    cost (linear in each ``p_i`` with the others fixed — the corner is
    picked by the sign of ``|S_c|·∂E[D_c]/∂p_i + c_i``). Non-members of a
    coalition stay pinned at 0; descending from the equilibrium profile
    the cost is monotone non-increasing, so it lower-bounds the NE cost
    within the same partition (the PoA denominator)."""
    n = costs.shape[0]
    member = _member_matrix(assign, m)
    dd = d_tab[1:] - d_tab[:-1]
    sizes = jnp.sum(member, axis=1)
    size_i = sizes[assign]                       # |S_c| of node i's coalition

    def sweep(p):
        f_cs = jax.vmap(poibin_pmf_recursive)(p * member)       # (M, N+1)

        def node(carry, i):
            f_cs, p = carry
            c = assign[i]
            loo = poibin_pmf_loo(f_cs[c], p[i])
            slope = loo[:-1] @ dd
            corner = jnp.where(size_i[i] * slope + costs[i] >= 0.0,
                               P_MIN, 1.0)
            best = jnp.where(member[c, i], corner, 0.0)
            f_new = poibin_convolve(loo, best)
            return (f_cs.at[c].set(f_new), p.at[i].set(best)), \
                jnp.abs(best - p[i])

        (_, p_new), deltas = jax.lax.scan(node, (f_cs, p), jnp.arange(n))
        return p_new, jnp.max(deltas)

    def cond(state):
        _, delta, it = state
        return (delta > 0.0) & (it < rounds)

    def body(state):
        p, _, it = state
        p_new, delta = sweep(p)
        return p_new, delta, it + 1

    p, _, _ = jax.lax.while_loop(
        cond, body, (p0, jnp.asarray(jnp.inf, p0.dtype), jnp.asarray(0)))
    return p


@functools.partial(jax.jit, static_argnames=("m", "rounds"))
def _partition_planner_vmapped(costs, d_tab, assign, p0, *, m, rounds):
    return jax.vmap(functools.partial(_partition_planner_one, m=m,
                                      rounds=rounds))(costs, d_tab, assign,
                                                      p0)


def partition_planner_batched(
    costs: jax.Array,
    dur: DurationModel | jax.Array,
    assign: jax.Array,
    p0: jax.Array,
    *,
    n_coalitions: int,
    rounds: int = 20,
) -> jax.Array:
    """Coalition-level planner: jitted per-coalition corner descent.

    Holds the partition fixed and minimizes its social cost over the
    members' participation (each coordinate minimum is exact — see
    :func:`~repro.core.asymmetric_batched.planner_batched`; here the
    corner sign uses the *coalition* size). Started from the equilibrium
    profile it lower-bounds the equilibrium's cost. Returns ``(B, N)``.
    """
    costs, _, d_tab, _, assign, b, n, m = _prepare_partition_batch(
        costs, jnp.zeros_like(jnp.asarray(costs, jnp.float64)), dur,
        n_coalitions, None, assign)
    p0 = jnp.broadcast_to(jnp.atleast_2d(jnp.asarray(p0, d_tab.dtype)),
                          (b, n))
    return _partition_planner_vmapped(costs, d_tab, assign, p0, m=m,
                                      rounds=int(rounds))


@dataclasses.dataclass(frozen=True)
class PartitionPoA:
    """Partition NE + certification + planner benchmark for a batch."""

    solution: PartitionSolution
    deviation: jax.Array   # (B,) max profitable deviation at the partition
    ne_cost: jax.Array     # (B,) social cost of the equilibrium
    opt_p: jax.Array       # (B, N) planner profile (descent from the NE)
    opt_cost: jax.Array    # (B,)
    poa: jax.Array         # (B,) partition PoA ≥ 1

    @property
    def batch(self) -> int:
        return self.solution.batch


def partition_poa_report(
    costs: jax.Array,
    gammas: jax.Array,
    dur: DurationModel | jax.Array,
    *,
    n_coalitions: int,
    cap: jax.Array | int | None = None,
    verify_grid: int = 64,
    planner_rounds: int = 20,
    **solver_kwargs,
) -> PartitionPoA:
    """Solve, certify, and benchmark a batch of coalition games."""
    sol = solve_partition(costs, gammas, dur, n_coalitions=n_coalitions,
                          cap=cap, **solver_kwargs)
    inner_kw = {k: solver_kwargs[k] for k in ("damping", "max_iters", "tol")
                if k in solver_kwargs}
    dev = verify_partition_batched(sol.costs, sol.gammas, dur, sol.assign,
                                   sol.p, n_coalitions=n_coalitions, cap=cap,
                                   grid=verify_grid, **inner_kw)
    ne_cost = partition_social_cost_batched(sol.costs, dur, sol.assign,
                                            sol.p, n_coalitions=n_coalitions)
    opt_p = partition_planner_batched(sol.costs, dur, sol.assign, sol.p,
                                      n_coalitions=n_coalitions,
                                      rounds=planner_rounds)
    opt_cost = partition_social_cost_batched(sol.costs, dur, sol.assign,
                                             opt_p,
                                             n_coalitions=n_coalitions)
    poa = ne_cost / jnp.maximum(opt_cost, 1e-12)
    return PartitionPoA(solution=sol, deviation=dev, ne_cost=ne_cost,
                        opt_p=opt_p, opt_cost=opt_cost, poa=poa)


# ---------------------------------------------------------------------------
# Python reference oracle (tests/test_property_coalition.py)
# ---------------------------------------------------------------------------

@jax.jit
def _reference_best_response(others, d_tab, cost, gamma):
    """Node's best response to the compact pmf of its coalition's others."""
    pmf = poibin_pmf_recursive(others)                 # (|S|,) support
    k = pmf.shape[0]
    slope = -(pmf @ (d_tab[1:k + 1] - d_tab[:k]))
    return best_response_given_slope(slope, cost, gamma)


@jax.jit
def _reference_node_utility(probs, d_tab, cost, gamma, p_i):
    """u_i from the compact pmf of all of the coalition's ``probs``."""
    pmf = poibin_pmf_recursive(probs)
    e_d = pmf @ d_tab[:pmf.shape[0]]
    return -e_d - gamma * log_aoi(p_i) - cost * p_i


def _reference_subgame_ne(costs, gammas, d_tab, members, *, damping,
                          max_iters, tol):
    """Compact-subgame Gauss-Seidel: the simplest statement of the inner
    NE — pmfs are built from the coalition's members only (no masks),
    matching the engine's fixed points to solver tolerance. The loop is
    plain Python; each node step is one compiled call, retraced once per
    coalition size."""
    members = list(members)
    p = {i: 0.5 for i in members}
    for _ in range(max_iters):
        delta = 0.0
        for i in members:
            others = jnp.asarray([p[j] for j in members if j != i],
                                 jnp.float64)
            br = float(_reference_best_response(others, d_tab, costs[i],
                                                gammas[i]))
            new_pi = (1.0 - damping) * p[i] + damping * br
            delta = max(delta, abs(new_pi - p[i]))
            p[i] = new_pi
        if delta < tol:
            break
    return p


def _reference_utility(costs, gammas, d_tab, members, p, i):
    """u_i at the compact subgame profile ``p`` (dict over ``members``)."""
    probs = jnp.asarray([p[j] for j in members], jnp.float64)
    return float(_reference_node_utility(probs, d_tab, costs[i], gammas[i],
                                         p[i]))


def partition_equilibrium_reference(
    costs,
    gammas,
    dur: DurationModel | jax.Array,
    *,
    n_coalitions: int,
    cap: int | None = None,
    assign0=None,
    damping: float = 0.5,
    max_iters: int = 200,
    tol: float = 1e-5,
    switch_tol: float = 1e-6,
    max_switches: int | None = None,
):
    """Plain-loop Python restatement of :func:`solve_partition` (the oracle).

    Both levels as plain loops over *compact* subgames: inner NEs are
    solved on each coalition's members only (list-of-indices, no masked
    fleet-width arrays), each swept in node-index order as the engine's
    masked sweep is (where a subgame has several NEs, the order selects
    one), and the outer loop re-solves every
    (node, coalition) candidate and applies the single best eligible
    switch — the same best-switch-first tie-breaking (row-major argmax
    over the (N, M) gain table) as the engine. Returns
    ``(assign, p, converged, switches)`` with ``assign`` a length-N list
    of ints and ``p`` a length-N list of floats (zeros are impossible:
    every node is always in some coalition).
    """
    import numpy as np

    d_tab = jnp.asarray(dur.table() if isinstance(dur, DurationModel)
                        else dur)
    costs = np.asarray(costs, np.float64)
    gammas = np.asarray(gammas, np.float64)
    n = costs.shape[0]
    m = int(n_coalitions)
    cap = n if cap is None else int(cap)
    if max_switches is None:
        max_switches = 4 * n * m
    assign = ([i % m for i in range(n)] if assign0 is None
              else [int(a) for a in assign0])

    def coalition_members(a, c):
        return [i for i in range(n) if a[i] == c]

    def solve_all(a):
        profiles = {}
        for c in range(m):
            profiles[c] = _reference_subgame_ne(
                costs, gammas, d_tab, coalition_members(a, c),
                damping=damping, max_iters=max_iters, tol=tol)
        return profiles

    switches = 0
    converged = False
    for _ in range(max_switches + 1):
        profiles = solve_all(assign)
        gain = np.full((n, m), -np.inf)
        sizes = [len(coalition_members(assign, c)) for c in range(m)]
        for i in range(n):
            c0 = assign[i]
            u_cur = _reference_utility(
                costs, gammas, d_tab, coalition_members(assign, c0),
                profiles[c0], i)
            for c in range(m):
                if c == c0 or sizes[c] >= cap:
                    continue
                joined = sorted(coalition_members(assign, c) + [i])
                p_cand = _reference_subgame_ne(
                    costs, gammas, d_tab, joined, damping=damping,
                    max_iters=max_iters, tol=tol)
                gain[i, c] = _reference_utility(
                    costs, gammas, d_tab, joined, p_cand, i) - u_cur
        flat = int(np.argmax(gain))
        best = gain.reshape(-1)[flat]
        if not best > switch_tol:
            converged = True
            break
        assign[flat // m] = flat % m
        switches += 1

    profiles = solve_all(assign)
    p = [profiles[assign[i]][i] for i in range(n)]
    return assign, p, converged, switches
