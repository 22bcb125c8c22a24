"""Batched heterogeneous-equilibrium engine: one XLA program per sweep.

The scalar solver in :mod:`repro.core.asymmetric` runs Python-loop
Gauss-Seidel with a full ``jnp.delete`` + O(N·N) DFT pmf recompute per node
per iteration — seconds for a single N=50 equilibrium, and a (costs, gammas)
scenario sweep is out of reach. This module rebuilds that layer as pure
fixed-shape ``lax`` control flow, jitted once over a batch of scenarios:

* **Damped Gauss-Seidel as a scan, in the frequency domain.** One
  round-robin sweep is a `lax.scan` over nodes, batched by hand with the
  fleets on the last axis. Node i's opponents' characteristic function
  (paper eq. 9's product, :mod:`repro.core.poibin`) is the running product
  of the factors already updated this sweep times the suffix product of
  the rest, which is rebuilt from the profile at the start of every sweep:
  products only, nothing divided out, no error carried across sweeps. The
  node's duration slope is one weighted sum over the spectrum's points,
  its exact closed-form best response is evaluated and its new factor is
  folded into the running product: O(N) elementwise work a node step, no
  inner loop. Sweeps iterate inside a `lax.while_loop` until the
  sweep-wide update delta drops below ``tol`` (identical semantics to the
  scalar loop), each fleet stopping at its own sweep.
* **Jitted certification.** :func:`verify_equilibrium_batched` evaluates
  every node's utility on a deviation grid in one shot — all N leave-one-out
  pmfs (a vmapped deconvolution), then a broadcast (N, G) utility table —
  no Python double loop.
* **Jitted planner.** The social cost ``N·E[D] + Σ c_i p_i`` is *linear* in
  each ``p_i`` with the others fixed (E[D] is multilinear), so the
  per-coordinate minimum sits at a corner determined by the sign of
  ``N·∂E[D]/∂p_i + c_i``; :func:`planner_batched` runs that coordinate
  descent with the certifier's deconvolution and matches the scalar
  grid-argmin planner's fixed points.
* **Heterogeneous PoA.** :func:`poa_report` packages NE + certification +
  planner + social costs for a whole scenario batch.

The solve is written for the batch; everything else is written
single-scenario and lifted with ``vmap`` in the jitted wrappers, so a
≥500-scenario (costs, gammas, dur) sweep at N=50 is one XLA dispatch (see
``benchmarks/heterogeneous_sweep.py``).

The batch-parallel surfaces (:func:`verify_equilibrium_batched`,
:func:`social_cost_batched`, and :func:`poa_report` through them) also
dispatch their Poisson-binomial work through the kernel layer: pass
``backend="pallas"`` to evaluate the whole batch's pmfs + leave-one-out
deconvolutions in the fused :mod:`repro.kernels.poibin_dft` kernel (fp32,
parity to ~1e-6). The default ``"ref"`` keeps the pre-existing vmapped jnp
programs bitwise-unchanged. The Gauss-Seidel NE solve itself stays jnp:
its per-node sweep is sequential (each node step uses the profile updated
by the previous node), which is not the kernel's batch-parallel shape.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.aoi import log_aoi
from repro.core.duration import DurationModel
from repro.core.poibin import (chi_size, materialised_factor,
                               poibin_chi_fold, poibin_chi_suffixes,
                               poibin_convolve, poibin_pmf_batched,
                               poibin_pmf_loo, poibin_pmf_loo_all,
                               poibin_pmf_recursive, unit_roots)

__all__ = [
    "P_MIN",
    "HeterogeneousSolution",
    "HeterogeneousPoA",
    "best_response_given_slope",
    "solve_heterogeneous",
    "verify_equilibrium_batched",
    "planner_batched",
    "social_cost_batched",
    "poa_report",
]

P_MIN = 1e-3  # matches repro.core.game / repro.core.asymmetric


def best_response_given_slope(slope: jax.Array, cost: jax.Array,
                              gamma: jax.Array) -> jax.Array:
    """Exact best response from the (utility-side) duration slope.

    With opponents fixed, ``u_i(p_i) = const + a·p_i - γ·log(1/p_i - 1/2)``
    where ``a = slope - cost`` and ``slope = -(E[d(m_-i+1)] - E[d(m_-i)])``.
    ``du/dp_i = a + 2γ/(p_i(2-p_i))``:

    * γ = 0: bang-bang on sign(a); exact indifference (a = 0) resolves to
      ``P_MIN``, matching the scalar solver.
    * γ > 0, a ≥ 0: utility strictly increasing ⇒ p = 1.
    * γ > 0, a < 0: the unique stationary point solves
      ``p(2-p) = -2γ/a``, i.e. ``p* = 1 - sqrt(1 + 2γ/a)`` (clipped to
      [P_MIN, 1]; the clip also absorbs the a → 0⁻ limit p* → 1).

    The a ≥ 0 quadratic branch is masked out by the outer ``where``, so its
    divisor is replaced by a benign -1 — a two-sided guard; dividing by a
    ``-1e-9`` sentinel (the old guard) produced a huge ``prod`` intermediate
    at a = 0 exactly.
    """
    a = slope - cost
    if_zero = jnp.where(a > 0.0, 1.0, P_MIN)
    denom = jnp.where(a < 0.0, a, -1.0)
    prod = -2.0 * gamma / denom          # p(2-p) at the stationary point
    disc = jnp.clip(1.0 - prod, 0.0, 1.0)
    interior = jnp.clip(1.0 - jnp.sqrt(disc), P_MIN, 1.0)
    return jnp.where(gamma <= 0.0, if_zero,
                     jnp.where(a >= 0.0, 1.0, interior))


# ---------------------------------------------------------------------------
# Gauss-Seidel fixed point (batched by hand: fleets on the last axis)
# ---------------------------------------------------------------------------

def _gs_fixed_point(costs, gammas, d_tab, p0, *, damping, max_iters, tol,
                    member=None):
    """Damped Gauss-Seidel NEs of a ``(B, N)`` batch of fleets.

    Runs in the frequency domain, on the first ``size // 2 + 1`` points of
    each fleet's characteristic function (``size = chi_size(N)``; the other
    points are their conjugates), with the fleets on the last axis. Node
    i's opponents are the nodes before it at their new values and the
    nodes after it at the sweep's old ones, so their characteristic
    function is the running product ``pre`` of the new factors times the
    suffix product of the old ones. The suffixes are rebuilt from the
    profile at the start of every sweep: nothing is divided out and no
    error carries from sweep to sweep. They start from the weighted DFT of
    the duration increments, so node i's slope ``-sum_m g[m] dd[m]`` (g
    the others' pmf) is one sum over the points. A node step is that sum,
    the closed-form best response and one factor folded into ``pre``.

    A fleet stops at its own sweep count, as under a vmapped while loop.
    ``member`` (``(B, N)`` bool) pins the nodes outside it at ``p = 0``,
    whose factor is exactly 1: the subgame on ``member`` at width N (the
    coalition engine). ``None`` leaves every node free.
    """
    b, n = costs.shape
    size = chi_size(n)
    half = size // 2 + 1
    w_re, w_im = unit_roots(size)
    w = (w_re[:half, None], w_im[:half, None])
    # -sum_m g[m] dd[m] = sum_k Re(chi_loo[k] c[k]) with c = -wt D: D the
    # DFT of dd zero-padded to size, wt 1/size at k = 0 and 2/size at each
    # conjugate pair. Sums, not dots: the TPU emulates a float64 dot with
    # nested loops.
    k = jnp.arange(half)
    ang = 2 * jnp.pi * ((k[:, None] * jnp.arange(n)[None, :]) % size) / size
    wt = jnp.where(k == 0, 1.0, 2.0)[:, None] / size
    dd = (d_tab[:, 1:] - d_tab[:, :-1]).T                        # (N, B)
    c_re = -wt * jnp.sum(jnp.cos(ang)[:, :, None] * dd, axis=1)  # (half, B)
    c_im = wt * jnp.sum(jnp.sin(ang)[:, :, None] * dd, axis=1)
    xs = (costs.T, gammas.T) + (() if member is None else (member.T,))
    one = jnp.ones((half, b), d_tab.dtype)

    def node(pre, x):
        t_re, t_im, pi, cost, gamma, *keep = x
        slope = jnp.sum(pre[0] * t_re - pre[1] * t_im, axis=0)  # utility side
        br = best_response_given_slope(slope, cost, gamma)
        new_pi = (1.0 - damping) * pi + damping * br
        if keep:
            new_pi = jnp.where(keep[0], new_pi, 0.0)
        return poibin_chi_fold(pre, materialised_factor(new_pi, w)), new_pi

    def sweep(p):
        t = poibin_chi_suffixes(p, w, (c_re, c_im))
        _, p_new = jax.lax.scan(node, (one, jnp.zeros_like(one)),
                                t + (p,) + xs)
        return p_new, jnp.max(jnp.abs(p_new - p), axis=0)

    def live(delta, it):
        return (delta >= tol) & (it < max_iters)

    def cond(state):
        _, delta, it = state
        return jnp.any(live(delta, it))

    def body(state):
        p, delta, it = state
        on = live(delta, it)
        p_new, delta_new = sweep(p)
        return (jnp.where(on, p_new, p), jnp.where(on, delta_new, delta),
                it + on)

    p, delta, iters = jax.lax.while_loop(
        cond, body, (p0.T, jnp.full((b,), jnp.inf, p0.dtype),
                     jnp.zeros((b,), int)))
    return p.T, delta < tol, iters


# The name is kept, though nothing here is vmapped: device-trace breakdowns
# list the solve's ops under ``jit__solve_vmapped``, so readings stay
# comparable across versions.
@functools.partial(jax.jit, static_argnames=("damping", "max_iters", "tol"))
def _solve_vmapped(costs, gammas, d_tab, p0, *, damping, max_iters, tol):
    return _gs_fixed_point(costs, gammas, d_tab, p0, damping=damping,
                           max_iters=max_iters, tol=tol)


# ---------------------------------------------------------------------------
# scenario-mesh sharding: pad + NamedSharding inputs, out_shardings results
# ---------------------------------------------------------------------------

#: (surface, mesh, axis, static kwargs…) -> jitted sharded program. jax.jit
#: caches per callable, so sharded programs must be built once per
#: (surface, mesh) — a fresh jit per call would retrace every sweep.
_SHARDED_PROGRAMS: dict = {}


def _batch_sharding(mesh, batch_axis):
    """NamedSharding + shard count for the scenario batch dim on ``mesh``.

    Resolved through :func:`repro.launch.sharding.scenario_batch_spec`
    (the MaxText-style rules engine), so the NE sweep places its batch on
    the same ``("pod", "data")`` candidates as every other engine.
    """
    from jax.sharding import NamedSharding

    from repro.launch.sharding import scenario_batch_spec, spec_axis_size

    spec = scenario_batch_spec(0, mesh, axis=batch_axis)
    return NamedSharding(mesh, spec), spec_axis_size(mesh, spec)


def _shard_batch_args(mesh, batch_axis, batch, arrays):
    """Edge-pad each leading-``batch`` array to shard-divisible size and
    ``device_put`` it with the resolved NamedSharding."""
    from repro.launch.sharding import pad_batch

    sharding, shards = _batch_sharding(mesh, batch_axis)
    return (tuple(jax.device_put(pad_batch(a, batch, shards), sharding)
                  for a in arrays), sharding)


def _sharded_program(key, builder):
    prog = _SHARDED_PROGRAMS.get(key)
    if prog is None:
        prog = _SHARDED_PROGRAMS[key] = builder()
    return prog


def _require_ref_backend(mesh, backend, *, site: str) -> bool:
    """Mesh sharding runs the vmapped jnp programs; resolve + guard.

    Returns True when the pallas kernel path should be taken (only ever
    with ``mesh=None``): the interpret-mode Pallas kernels are not
    partitionable by GSPMD, so combining them with a scenario mesh raises
    rather than silently gathering the batch onto one device.
    """
    from repro.kernels import ops as kernel_ops  # lazy: keep core light

    pallas = kernel_ops.resolve_backend(
        backend, default="ref", site=site) == "pallas"
    if pallas and mesh is not None:
        raise ValueError(
            f"{site}: mesh sharding is only supported on the ref backend "
            "(the interpret-mode Pallas kernels cannot be partitioned)")
    return pallas


@dataclasses.dataclass(frozen=True)
class HeterogeneousSolution:
    """A vmapped batch of asymmetric-NE solves."""

    costs: jax.Array       # (B, N)
    gammas: jax.Array      # (B, N)
    p: jax.Array           # (B, N) fixed-point profiles
    converged: jax.Array   # (B,) bool
    iters: jax.Array       # (B,) Gauss-Seidel sweeps run

    @property
    def batch(self) -> int:
        return int(self.p.shape[0])

    def single(self) -> tuple[jax.Array, bool, int]:
        """The (profile, converged, iters) triple of a B = 1 solve."""
        if self.batch != 1:
            raise ValueError(
                f"single() called on a batch of {self.batch} scenarios")
        return self.p[0], bool(self.converged[0]), int(self.iters[0])


def _prepare_batch(costs, gammas, dur, p0):
    d_tab = dur.table() if isinstance(dur, DurationModel) else jnp.asarray(dur)
    costs = jnp.atleast_2d(jnp.asarray(costs, d_tab.dtype))
    gammas = jnp.atleast_2d(jnp.asarray(gammas, d_tab.dtype))
    try:
        shape = jnp.broadcast_shapes(costs.shape, gammas.shape)
    except ValueError as e:
        raise ValueError(f"costs {costs.shape} vs gammas {gammas.shape}: {e}")
    costs = jnp.broadcast_to(costs, shape)
    gammas = jnp.broadcast_to(gammas, shape)
    b, n = shape
    if d_tab.ndim == 1:
        d_tab = jnp.broadcast_to(d_tab, (b,) + d_tab.shape)
    if d_tab.shape != (b, n + 1):
        raise ValueError(f"duration table {d_tab.shape}, want {(b, n + 1)}")
    if p0 is None:
        p0 = jnp.full((b, n), 0.5, d_tab.dtype)
    else:
        p0 = jnp.broadcast_to(jnp.atleast_2d(jnp.asarray(p0, d_tab.dtype)),
                              (b, n))
    return costs, gammas, d_tab, p0


def solve_heterogeneous(
    costs: jax.Array,
    gammas: jax.Array,
    dur: DurationModel | jax.Array,
    *,
    p0: jax.Array | None = None,
    damping: float = 0.5,
    max_iters: int = 200,
    tol: float = 1e-5,
    mesh=None,
    batch_axis=None,
) -> HeterogeneousSolution:
    """Solve a batch of heterogeneous games in one jitted program.

    Args:
        costs / gammas: ``(N,)`` for a single game or ``(B, N)`` for a batch;
            the two broadcast against each other in either direction, so e.g.
            ``costs (N,)`` with ``gammas (B, N)`` runs a γ-sweep over one
            cost vector.
        dur: a shared :class:`DurationModel`, a shared ``(N+1,)`` duration
            table, or a per-scenario ``(B, N+1)`` stack of tables.
        p0: initial profile(s); defaults to the all-0.5 profile like the
            scalar solver.
        damping / max_iters / tol: Gauss-Seidel controls with the scalar
            solver's defaults and semantics (``iters`` counts round-robin
            sweeps; convergence is max per-node update < tol within a sweep).
        mesh: optional :class:`jax.sharding.Mesh` — shard the scenario
            batch over its data-parallel axes (``batch_axis`` overrides
            the rules-table candidates). Arbitrary ``B`` is edge-padded to
            shard-divisibility and results are sliced back; ``mesh=None``
            (default) is the unchanged single-device program. Note the
            batched while_loop runs until every lane (padding included)
            converges, so wall-clock is the max over the shard.
    """
    costs, gammas, d_tab, p0 = _prepare_batch(costs, gammas, dur, p0)
    statics = (float(damping), int(max_iters), float(tol))
    if mesh is None:
        p, conv, iters = _solve_vmapped(costs, gammas, d_tab, p0,
                                        damping=statics[0],
                                        max_iters=int(max_iters),
                                        tol=statics[2])
    else:
        b = costs.shape[0]
        args, sharding = _shard_batch_args(
            mesh, batch_axis, b, (costs, gammas, d_tab, p0))

        def build():
            solve = functools.partial(
                _gs_fixed_point, damping=statics[0],
                max_iters=int(max_iters), tol=statics[2])
            return jax.jit(solve, in_shardings=sharding,
                           out_shardings=sharding)

        prog = _sharded_program(("solve", mesh, batch_axis) + statics, build)
        p, conv, iters = prog(*args)
        p, conv, iters = p[:b], conv[:b], iters[:b]
    return HeterogeneousSolution(costs=costs, gammas=gammas, p=p,
                                 converged=conv, iters=iters)


# ---------------------------------------------------------------------------
# Jitted certification: vectorized unilateral-deviation grid
# ---------------------------------------------------------------------------

def _loo_tables(p, d_tab):
    """Per-node E[d(m_-i)] and its p_i-slope from one pmf + N deconvolutions.

    Returns ``(base, slope)``, both (N,): with opponents fixed,
    ``E[D](q) = base_i + q·slope_i`` for node i playing q.
    """
    dd = d_tab[1:] - d_tab[:-1]
    f = poibin_pmf_recursive(p)
    loo = jax.vmap(poibin_pmf_loo, in_axes=(None, 0))(f, p)   # (N, N+1)
    base = loo[:, :-1] @ d_tab[:-1]
    slope = loo[:, :-1] @ dd
    return base, slope


def _verify_one(costs, gammas, d_tab, p, *, grid):
    base, slope = _loo_tables(p, d_tab)
    gridv = jnp.linspace(P_MIN, 1.0, grid).astype(p.dtype)
    aoi_dev = log_aoi(gridv)
    u_dev = (-(base[:, None] + gridv[None, :] * slope[:, None])
             - gammas[:, None] * aoi_dev[None, :]
             - costs[:, None] * gridv[None, :])                # (N, G)
    u_eq = (-(base + p * slope) - gammas * log_aoi(p) - costs * p)  # (N,)
    return jnp.maximum(jnp.max(u_dev - u_eq[:, None]), 0.0)


@functools.partial(jax.jit, static_argnames=("grid",))
def _verify_vmapped(costs, gammas, d_tab, p, *, grid):
    return jax.vmap(functools.partial(_verify_one, grid=grid))(
        costs, gammas, d_tab, p)


@functools.partial(jax.jit, static_argnames=("grid",))
def _verify_vmapped_pallas(costs, gammas, d_tab, p, *, grid):
    """Kernel-path certifier: one fused poibin program for the whole batch,
    then the same broadcast deviation-utility table as :func:`_verify_one`
    with a leading batch axis."""
    _, loo = poibin_pmf_loo_all(p, backend="pallas")      # (B,S), (B,N,S)
    dd = d_tab[:, 1:] - d_tab[:, :-1]
    base = jnp.einsum("bns,bs->bn", loo[:, :, :-1], d_tab[:, :-1])
    slope = jnp.einsum("bns,bs->bn", loo[:, :, :-1], dd)
    gridv = jnp.linspace(P_MIN, 1.0, grid).astype(p.dtype)
    aoi_dev = log_aoi(gridv)
    u_dev = (-(base[..., None] + gridv[None, None, :] * slope[..., None])
             - gammas[..., None] * aoi_dev[None, None, :]
             - costs[..., None] * gridv[None, None, :])   # (B, N, G)
    u_eq = (-(base + p * slope) - gammas * log_aoi(p) - costs * p)  # (B, N)
    return jnp.maximum(
        jnp.max(u_dev - u_eq[..., None], axis=(1, 2)), 0.0)


def verify_equilibrium_batched(
    costs: jax.Array,
    gammas: jax.Array,
    dur: DurationModel | jax.Array,
    p: jax.Array,
    *,
    grid: int = 64,
    backend: str | None = None,
    mesh=None,
    batch_axis=None,
) -> jax.Array:
    """Max profitable unilateral deviation per scenario (0 at an exact NE).

    One jitted program: all N leave-one-out pmfs via vmapped deconvolution,
    then an (N, grid) deviation-utility table per scenario — no Python loops.
    Accepts the same single-game / batched shapes as
    :func:`solve_heterogeneous`; returns ``(B,)``.

    ``backend="pallas"`` computes the pmf/leave-one-out block in the fused
    :mod:`repro.kernels.poibin_dft` kernel (fp32 parity); the default
    ``"ref"`` is the bitwise-unchanged vmapped jnp program. ``mesh`` shards
    the scenario batch (ref backend only; see :func:`solve_heterogeneous`).
    """
    costs, gammas, d_tab, p = _prepare_batch(costs, gammas, dur, p)
    if _require_ref_backend(mesh, backend,
                            site="ne.verify_equilibrium_batched"):
        return _verify_vmapped_pallas(costs, gammas, d_tab, p,
                                      grid=int(grid))
    if mesh is None:
        return _verify_vmapped(costs, gammas, d_tab, p, grid=int(grid))
    b = costs.shape[0]
    args, sharding = _shard_batch_args(
        mesh, batch_axis, b, (costs, gammas, d_tab, p))

    def build():
        return jax.jit(
            jax.vmap(functools.partial(_verify_one, grid=int(grid))),
            in_shardings=sharding, out_shardings=sharding)

    prog = _sharded_program(("verify", mesh, batch_axis, int(grid)), build)
    return prog(*args)[:b]


# ---------------------------------------------------------------------------
# Jitted heterogeneity-aware planner + social cost + PoA report
# ---------------------------------------------------------------------------

def _social_cost_one(costs, d_tab, p):
    n = costs.shape[0]
    f = poibin_pmf_recursive(p)
    return n * (f @ d_tab) + costs @ p


@jax.jit
def _social_cost_vmapped(costs, d_tab, p):
    return jax.vmap(_social_cost_one)(costs, d_tab, p)


@jax.jit
def _social_cost_vmapped_pallas(costs, d_tab, p):
    f = poibin_pmf_batched(p, backend="pallas")           # (B, S)
    n = costs.shape[1]
    return n * jnp.sum(f * d_tab, axis=1) + jnp.sum(costs * p, axis=1)


def social_cost_batched(costs: jax.Array, dur: DurationModel | jax.Array,
                        p: jax.Array, *,
                        backend: str | None = None,
                        mesh=None, batch_axis=None) -> jax.Array:
    """``Σ_i (E[D] + c_i p_i) = N·E[D] + Σ c_i p_i`` per scenario, ``(B,)``.

    ``backend="pallas"`` evaluates the batch's pmfs in the DFT kernel;
    the default ``"ref"`` keeps the vmapped convolution-recursion program
    bitwise-unchanged. ``mesh`` shards the scenario batch (ref backend
    only; see :func:`solve_heterogeneous`).
    """
    costs, _, d_tab, p = _prepare_batch(costs, jnp.zeros_like(costs), dur, p)
    if _require_ref_backend(mesh, backend, site="ne.social_cost_batched"):
        return _social_cost_vmapped_pallas(costs, d_tab, p)
    if mesh is None:
        return _social_cost_vmapped(costs, d_tab, p)
    b = costs.shape[0]
    args, sharding = _shard_batch_args(mesh, batch_axis, b,
                                       (costs, d_tab, p))

    def build():
        return jax.jit(jax.vmap(_social_cost_one),
                       in_shardings=sharding, out_shardings=sharding)

    prog = _sharded_program(("social_cost", mesh, batch_axis), build)
    return prog(*args)[:b]


def _planner_one(costs, d_tab, p0, *, rounds):
    n = costs.shape[0]
    dd = d_tab[1:] - d_tab[:-1]

    def sweep(p):
        f = poibin_pmf_recursive(p)

        def node(carry, i):
            f, p = carry
            loo = poibin_pmf_loo(f, p[i])
            slope = loo[:-1] @ dd                 # ∂E[D]/∂p_i, others fixed
            # Social cost is linear in p_i: N·slope + c_i decides the corner.
            best = jnp.where(n * slope + costs[i] >= 0.0, P_MIN, 1.0)
            f_new = poibin_convolve(loo, best)
            return (f_new, p.at[i].set(best)), jnp.abs(best - p[i])

        (_, p_new), deltas = jax.lax.scan(node, (f, p), jnp.arange(n))
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


@functools.partial(jax.jit, static_argnames=("rounds",))
def _planner_vmapped(costs, d_tab, p0, *, rounds):
    return jax.vmap(functools.partial(_planner_one, rounds=rounds))(
        costs, d_tab, p0)


def planner_batched(
    costs: jax.Array,
    dur: DurationModel | jax.Array,
    p0: jax.Array,
    *,
    rounds: int = 20,
    mesh=None,
    batch_axis=None,
) -> jax.Array:
    """Heterogeneity-aware planner: jitted round-robin coordinate descent.

    Each coordinate update is *exact* (the social cost is linear in one
    ``p_i``, so the minimum is a corner picked by the sign of
    ``N·∂E[D]/∂p_i + c_i``), which reproduces the scalar planner's
    grid-argmin fixed points without any grid. Monotone non-increasing, so
    started from an NE profile its cost lower-bounds the NE cost — the PoA
    denominator. Returns ``(B, N)`` profiles. ``mesh`` shards the scenario
    batch (see :func:`solve_heterogeneous`).
    """
    costs, _, d_tab, p0 = _prepare_batch(costs, jnp.zeros_like(costs), dur, p0)
    if mesh is None:
        return _planner_vmapped(costs, d_tab, p0, rounds=int(rounds))
    b = costs.shape[0]
    args, sharding = _shard_batch_args(mesh, batch_axis, b,
                                       (costs, d_tab, p0))

    def build():
        return jax.jit(
            jax.vmap(functools.partial(_planner_one, rounds=int(rounds))),
            in_shardings=sharding, out_shardings=sharding)

    prog = _sharded_program(("planner", mesh, batch_axis, int(rounds)), build)
    return prog(*args)[:b]


@dataclasses.dataclass(frozen=True)
class HeterogeneousPoA:
    """NE + certification + planner benchmark for a scenario batch."""

    solution: HeterogeneousSolution
    deviation: jax.Array   # (B,) max profitable unilateral deviation at NE
    ne_cost: jax.Array     # (B,) social cost of the reached profile
    opt_p: jax.Array       # (B, N) planner profile (descent from the NE)
    opt_cost: jax.Array    # (B,)
    poa: jax.Array         # (B,) heterogeneous PoA ≥ 1

    @property
    def batch(self) -> int:
        return self.solution.batch


def poa_report(
    costs: jax.Array,
    gammas: jax.Array,
    dur: DurationModel | jax.Array,
    *,
    verify_grid: int = 64,
    planner_rounds: int = 20,
    backend: str | None = None,
    mesh=None,
    batch_axis=None,
    **solver_kwargs,
) -> HeterogeneousPoA:
    """Solve, certify, and benchmark a batch of heterogeneous scenarios.

    ``backend`` routes the certification and social-cost evaluations
    through :mod:`repro.kernels.poibin_dft` when ``"pallas"`` (the NE
    solve and planner stay jnp — their sweeps are sequential per node);
    the default ``"ref"`` is bitwise-unchanged. ``mesh``/``batch_axis``
    shard every stage's scenario batch over the mesh's data axes (ref
    backend only; see :func:`solve_heterogeneous`).
    """
    sol = solve_heterogeneous(costs, gammas, dur, mesh=mesh,
                              batch_axis=batch_axis, **solver_kwargs)
    dev = verify_equilibrium_batched(sol.costs, sol.gammas, dur, sol.p,
                                     grid=verify_grid, backend=backend,
                                     mesh=mesh, batch_axis=batch_axis)
    ne_cost = social_cost_batched(sol.costs, dur, sol.p, backend=backend,
                                  mesh=mesh, batch_axis=batch_axis)
    opt_p = planner_batched(sol.costs, dur, sol.p, rounds=planner_rounds,
                            mesh=mesh, batch_axis=batch_axis)
    opt_cost = social_cost_batched(sol.costs, dur, opt_p, backend=backend,
                                   mesh=mesh, batch_axis=batch_axis)
    poa = ne_cost / jnp.maximum(opt_cost, 1e-12)
    return HeterogeneousPoA(solution=sol, deviation=dev, ne_cost=ne_cost,
                            opt_p=opt_p, opt_cost=opt_cost, poa=poa)
