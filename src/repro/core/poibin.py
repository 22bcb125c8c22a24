"""Poisson-Binomial distribution of the number of participating nodes.

Paper eq. (9): closed-form DFT expression for the pmf of ``m = sum_i X_i``
with independent ``X_i ~ Bernoulli(p_i)`` (Fernandez & Williams, 2010), and
eq. (8): the expected task duration ``E[D] = sum_k d(k) P[m=k]``.

Everything scalar here is pure JAX (a real-arithmetic DFT) and
differentiable in the participation probabilities — the NE solver in
:mod:`repro.core.game` differentiates straight through this pmf.

The *batched* entry points (:func:`poibin_pmf_batched`,
:func:`poibin_pmf_loo_all`) additionally dispatch through the kernel layer
(:mod:`repro.kernels.poibin_dft` via ``repro.kernels.ops``): pass
``backend="pallas"`` — or set ``REPRO_KERNEL_BACKEND=pallas`` — to fuse a
whole (B, N) scenario batch into one Pallas program. The kernel path is
fp32 and **not differentiable**; the default ``"ref"`` backend keeps the
pure-jnp vmapped math (bitwise-identical to calling the scalar functions
under ``jax.vmap`` yourself).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "unit_roots",
    "chi_size",
    "bernoulli_factor",
    "materialised_factor",
    "poibin_chi_fold",
    "poibin_chi",
    "poibin_chi_suffixes",
    "poibin_pmf",
    "poibin_pmf_recursive",
    "poibin_convolve",
    "poibin_pmf_loo",
    "poibin_pmf_batched",
    "poibin_pmf_loo_all",
    "poibin_mean",
    "poibin_cdf",
    "expected_duration",
    "symmetric_pmf",
]


def unit_roots(size: int) -> tuple[jax.Array, jax.Array]:
    """``(cos, sin)`` of the ``size`` points ``w^n = exp(j 2 pi n/size)``."""
    ang = 2 * jnp.pi * jnp.arange(size) / size
    return jnp.cos(ang), jnp.sin(ang)


def chi_size(n_nodes: int) -> int:
    """Odd transform length ``>= n_nodes + 1`` for the solver's spectra.

    A real pmf's characteristic function is conjugate-symmetric,
    ``chi[size - n] = conj(chi[n])``. At an odd length every point but
    ``n = 0`` has such a partner, so the first ``size // 2 + 1`` points
    carry the whole spectrum, with no lone point at ``w = -1``. Any length
    above the support recovers the pmf exactly.
    """
    return n_nodes + 1 if n_nodes % 2 == 0 else n_nodes + 2


def bernoulli_factor(p_k: jax.Array, w: tuple[jax.Array, jax.Array]
                     ) -> tuple[jax.Array, jax.Array]:
    """Node k's factor ``1 - p_k + p_k w`` at the points ``w``, as a
    (re, im) pair. At ``p_k = 0`` it is exactly ``(1, 0)``."""
    w_re, w_im = w
    return p_k * (w_re - 1.0) + 1.0, p_k * w_im


def materialised_factor(p_k: jax.Array, w: tuple[jax.Array, jax.Array]
                        ) -> tuple[jax.Array, jax.Array]:
    """:func:`bernoulli_factor`, computed once. Fused into a product, the
    TPU's float64 emulation recomputes the factor for each part of the
    product; the Gauss-Seidel solver's steps, which do little else, halve
    their work by materialising it."""
    return jax.lax.optimization_barrier(bernoulli_factor(p_k, w))


def poibin_chi_fold(chi, t) -> tuple[jax.Array, jax.Array]:
    """``chi`` times a factor ``t`` (both (re, im) pairs). The factor of
    ``p_k = 0`` is exactly ``1``, so ``chi`` comes back unchanged."""
    re, im = chi
    t_re, t_im = t
    return re * t_re - im * t_im, re * t_im + im * t_re


def poibin_chi(p: jax.Array, w: tuple[jax.Array, jax.Array]
               ) -> tuple[jax.Array, jax.Array]:
    """Characteristic function ``prod_k (1 - p_k + p_k w)`` at the points
    ``w`` (from :func:`unit_roots`), as a (re, im) pair: the TPU compiler
    has no complex128."""
    def step(chi, p_k):
        return poibin_chi_fold(chi, bernoulli_factor(p_k, w)), None

    one = jnp.ones(w[0].shape, jnp.result_type(p, w[0]))
    chi, _ = jax.lax.scan(step, (one, jnp.zeros_like(one)), p)
    return chi


def poibin_chi_suffixes(p: jax.Array, w: tuple[jax.Array, jax.Array],
                        chi0: tuple[jax.Array, jax.Array]
                        ) -> tuple[jax.Array, jax.Array]:
    """Exclusive suffix products ``chi0 * prod_{k > i} (1 - p_k + p_k w)``
    for every node i, stacked on a new leading axis.

    Node i's leave-one-out characteristic function is the product of the
    factors before it (:func:`poibin_chi` of ``p[:i]``) and this suffix
    with ``chi0 = 1``: products only, so nothing is divided out."""
    def step(chi, p_k):
        return poibin_chi_fold(chi, materialised_factor(p_k, w)), chi

    _, out = jax.lax.scan(step, chi0, p, reverse=True)
    return out


@jax.jit
def poibin_pmf(p: jax.Array) -> jax.Array:
    """Pmf of the Poisson-Binomial distribution via the DFT closed form.

    Implements paper eq. (9)::

        P[m] = (1/(N+1)) * sum_{n=0}^{N} exp(-j 2 pi n m/(N+1))
                  * prod_{k=1}^{N} [p_k (exp(j 2 pi n/(N+1)) - 1) + 1]

    in real arithmetic (:func:`poibin_chi`).

    Args:
        p: ``(N,)`` participation probabilities in [0, 1].

    Returns:
        ``(N+1,)`` real pmf over m = 0..N.
    """
    p = jnp.asarray(p)
    size = p.shape[0] + 1
    chi_re, chi_im = poibin_chi(p, unit_roots(size))
    # Re[sum_n exp(-j theta_mn) chi_n] with theta_mn = 2 pi m n/(N+1).
    theta = 2 * jnp.pi * jnp.outer(jnp.arange(size), jnp.arange(size)) / size
    pmf = jnp.sum(jnp.cos(theta) * chi_re + jnp.sin(theta) * chi_im,
                  axis=1) / size
    # Numerical cleanup: clip tiny negatives, renormalize.
    pmf = jnp.clip(pmf, 0.0, 1.0)
    return pmf / jnp.sum(pmf)


def poibin_pmf_recursive(p: jax.Array) -> jax.Array:
    """Pmf via the stable O(N^2) convolution recursion (oracle for tests).

    ``f_{k+1} = conv(f_k, [1-p_k, p_k])`` — exact up to float error, no DFT.
    """
    p = jnp.asarray(p)
    n_nodes = p.shape[0]
    size = n_nodes + 1

    def step(pmf, pk):
        shifted = jnp.concatenate([jnp.zeros((1,), pmf.dtype), pmf[:-1]])
        return pmf * (1.0 - pk) + shifted * pk, None

    init = jnp.zeros((size,), p.dtype).at[0].set(1.0)
    pmf, _ = jax.lax.scan(step, init, p)
    return pmf


def poibin_convolve(pmf: jax.Array, p_k: jax.Array) -> jax.Array:
    """Fold one Bernoulli(``p_k``) factor into a Poisson-Binomial pmf.

    ``pmf`` is a fixed-length ``(S,)`` array whose top entry must be zero
    (the support grows by one); the result stays ``(S,)``. This is the single
    step of :func:`poibin_pmf_recursive` exposed so the heterogeneous
    planners' coordinate descent can update the pmf in O(N) instead of a
    full O(N²) recompute per node.
    """
    shifted = jnp.concatenate([jnp.zeros((1,), pmf.dtype), pmf[:-1]])
    return pmf * (1.0 - p_k) + shifted * p_k


def poibin_pmf_loo(pmf: jax.Array, p_i: jax.Array) -> jax.Array:
    """Leave-one-out deconvolution: divide node i's Bernoulli factor back out.

    Given the ``(N+1,)`` pmf of all N nodes and node i's probability ``p_i``,
    returns the ``(N+1,)`` pmf of the other N-1 nodes (support 0..N-1; the
    last entry is zero). This inverts :func:`poibin_convolve` exactly:
    ``poibin_convolve(poibin_pmf_loo(f, p_i), p_i) == f`` up to float error.

    Numerics: the division recursion amplifies error by ``p/(1-p)`` per step
    run forward and by ``(1-p)/p`` run backward, so we run

    * forward  ``g[k] = (f[k] - p_i·g[k-1]) / (1-p_i)`` when ``p_i ≤ 1/2``,
    * backward ``g[k] = (f[k+1] - (1-p_i)·g[k+1]) / p_i`` when ``p_i > 1/2``,

    keeping the per-step amplification ≤ 1 for every ``p_i`` in [0, 1]
    including the ``p_i ∈ {0, 1}`` corners (where the recursion degenerates
    to a copy/shift). Both branches are fixed-shape `lax.scan`s, so this is
    jit/vmap-safe.
    """
    pmf = jnp.asarray(pmf)
    p_i = jnp.asarray(p_i, pmf.dtype)
    q_i = 1.0 - p_i
    use_fwd = p_i <= 0.5
    # Safe denominators: the unused branch still executes under jit, so give
    # it a benign divisor instead of a possible 0.
    q_safe = jnp.where(use_fwd, q_i, 0.5)
    p_safe = jnp.where(use_fwd, 0.5, p_i)

    def fwd(g_prev, f_k):
        g_k = (f_k - p_i * g_prev) / q_safe
        return g_k, g_k

    _, g_fwd = jax.lax.scan(fwd, jnp.zeros((), pmf.dtype), pmf[:-1])

    def bwd(g_next, f_k1):
        g_k = (f_k1 - q_i * g_next) / p_safe
        return g_k, g_k

    _, g_bwd = jax.lax.scan(bwd, jnp.zeros((), pmf.dtype), pmf[1:],
                            reverse=True)

    g = jnp.where(use_fwd, g_fwd, g_bwd)
    return jnp.concatenate([g, jnp.zeros((1,), pmf.dtype)])


def poibin_pmf_batched(p: jax.Array, *, backend: str | None = None
                       ) -> jax.Array:
    """Pmfs of a whole ``(B, N)`` probability-matrix batch, ``(B, N+1)``.

    ``backend="pallas"`` runs the batched DFT kernel
    (:mod:`repro.kernels.poibin_dft`, fp32, one program for the batch);
    the default ``"ref"`` is exactly ``jax.vmap(poibin_pmf)`` (float64
    under x64, differentiable).
    """
    from repro.kernels import ops as kernel_ops  # lazy: keep core light

    if kernel_ops.resolve_backend(
            backend, default="ref", site="poibin.pmf_batched") == "pallas":
        return kernel_ops.poibin_pmf(p, backend="pallas")
    return jax.vmap(poibin_pmf)(p)


def poibin_pmf_loo_all(p: jax.Array, *, backend: str | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """All leave-one-out pmfs of a ``(B, N)`` batch in one pass.

    Returns ``(pmf (B, N+1), loo (B, N, N+1))`` where ``loo[b, i]`` is the
    pmf of scenario b's nodes excluding node i. ``backend="pallas"`` fuses
    DFT pmf + N deconvolutions per scenario into one kernel; the default
    ``"ref"`` builds the pmf with the stable convolution recursion and
    deconvolves it (``vmap``-ed :func:`poibin_pmf_loo`) — the exact op
    sequence of the heterogeneous-game certifier, kept as its bitwise
    oracle.
    """
    from repro.kernels import ops as kernel_ops  # lazy: keep core light

    if kernel_ops.resolve_backend(
            backend, default="ref", site="poibin.pmf_loo_all") == "pallas":
        return kernel_ops.poibin(p, backend="pallas")
    pmf = jax.vmap(poibin_pmf_recursive)(p)
    loo = jax.vmap(jax.vmap(poibin_pmf_loo, in_axes=(None, 0)))(pmf, p)
    return pmf, loo


def poibin_mean(p: jax.Array) -> jax.Array:
    """E[m] = sum_i p_i."""
    return jnp.sum(p)


def poibin_cdf(p: jax.Array) -> jax.Array:
    """Cdf over m = 0..N."""
    return jnp.cumsum(poibin_pmf(p))


def symmetric_pmf(p_scalar: jax.Array, n_nodes: int) -> jax.Array:
    """Pmf when all nodes share probability ``p`` (Binomial(N, p)) via eq. (9)."""
    return poibin_pmf(jnp.full((n_nodes,), p_scalar))


def expected_duration(p: jax.Array, duration_of_k: jax.Array) -> jax.Array:
    """Paper eq. (8): ``E[D] = sum_{i=0}^{N} d(i) P[m=i]``.

    Args:
        p: ``(N,)`` participation probabilities.
        duration_of_k: ``(N+1,)`` rounds-to-converge when exactly k nodes
            participate each round (see :mod:`repro.core.duration`).
    """
    pmf = poibin_pmf(p)
    return jnp.sum(pmf * duration_of_k)
