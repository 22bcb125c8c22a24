"""Batched Poisson-Binomial Pallas TPU kernel (paper eq. (9) + leave-one-out).

The game layer's hot loop evaluates, for a whole batch of scenarios, the
pmf of the participant count ``m = Σ_i Bernoulli(p_i)`` and — for
equilibrium certification — the N *leave-one-out* pmfs "everyone except
node i". One kernel invocation fuses both over a (B, N) probability
matrix:

* **DFT pmf** (eq. (9)): the characteristic function on the (N+1)-point
  unit circle, ``χ(n) = Π_k [p_k(ω^n − 1) + 1]``, is accumulated as an
  explicit (re, im) pair over a ``fori_loop`` of the N Bernoulli factors
  (Pallas TPU has no complex dtype), then inverted with two MXU matmuls
  against precomputed (S, S) cos/sin DFT matrices (S = N+1), clipped to
  [0, 1] and renormalized — the same cleanup as
  :func:`repro.core.poibin.poibin_pmf`.
* **Leave-one-out deconvolution**: node i's ``[1-p_i, p_i]`` factor is
  divided back out of the full pmf for *all N nodes at once* — the (B, N)
  lanes run the forward recursion ``g_k = (f_k − p·g_{k-1})/(1−p)`` where
  ``p ≤ 1/2`` and the backward recursion ``g_k = (f_{k+1} − (1−p)·g_{k+1})/p``
  where ``p > 1/2`` (per-step error amplification ≤ 1, including the
  p ∈ {0, 1} corners), exactly mirroring
  :func:`repro.core.poibin.poibin_pmf_loo`.

* grid = (batch_tiles,); each tile owns a (BB, N) probability slab, the
  shared (S, S) cos/sin matrices, and writes a (BB, S) pmf tile plus —
  with ``with_loo`` — a (BB, S, N) leave-one-out tile (support axis
  second-to-last so the per-step dynamic writes land on a contiguous
  (BB, 1, N) slab; the public wrapper transposes to (B, N, S)).
* Per-tile VMEM at BB = 8, N = 64 fp32: ~0.3 MB (p 2 KB + 2·S² DFT 33 KB +
  pmf 2 KB + loo 133 KB + recursion carries) — far under budget; the
  matmuls are (BB, S)·(S, S) MXU work, the recursions VPU work.
* dtype policy: inputs are cast to fp32 in the wrapper and all in-kernel
  arithmetic is fp32; outputs are cast back to ``p_mat.dtype`` (the game
  layer runs x64, so the pallas path is parity-to-tolerance, ~1e-6).

Oracle: :func:`repro.kernels.ref.poibin_dft_ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Constant block index for index maps: an int32 scalar, since a Python 0
# traces as int64 under x64 and Mosaic rejects 64-bit block indices.
_ZERO = np.int32(0)


def _column(x, k):
    """``x[:, k:k+1]`` for a traced ``k``, as a masked lane reduction.

    Mosaic has no value-level dynamic slice; adding zeros to the one
    selected entry keeps the result exact.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(lane == k, x, np.float32(0.0)), axis=1,
                   keepdims=True)


def _loop(n: int, body, init):
    """``fori_loop(0, n, body, init)`` with an int32 index.

    Under x64 ``fori_loop``'s index comes out int64, even from
    ``np.int32`` bounds, and Mosaic cannot lower it.
    """
    def step(carry, _):
        k, x = carry
        return (k + 1, body(k, x)), None

    (_, out), _ = jax.lax.scan(step, (np.int32(0), init), None, length=n)
    return out


def _pmf_body(p, cos, sin, size: int, n: int):
    """Shared DFT-pmf computation: (BB, N) fp32 probs -> (BB, S) pmf."""
    omega_re = cos[1:2, :]                 # cos(2π n / S), n = 0..S-1
    omega_im = sin[1:2, :]                 # sin(2π n / S)

    def chi_step(k, carry):
        re, im = carry                     # (BB, S) running complex product
        pk = _column(p, k)                 # (BB, 1)
        t_re = pk * (omega_re - 1.0) + 1.0
        t_im = pk * omega_im
        return re * t_re - im * t_im, re * t_im + im * t_re

    ones = jnp.ones((p.shape[0], size), jnp.float32)
    chi_re, chi_im = _loop(n, chi_step, (ones, ones * 0.0))
    # Re[Σ_n e^{-2πi nm/S} χ(n)] / S; cos/sin matrices are symmetric.
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    raw = (dot(chi_re, cos) + dot(chi_im, sin)) / size
    raw = jnp.minimum(jnp.maximum(raw, 0.0), 1.0)    # clip, f32 bounds
    return raw / jnp.sum(raw, axis=1, keepdims=True)


def _kernel_pmf(p_ref, cos_ref, sin_ref, pmf_ref, *, n: int):
    pmf_ref[...] = _pmf_body(p_ref[...].astype(jnp.float32), cos_ref[...],
                             sin_ref[...], n + 1, n)


def _kernel_loo(p_ref, cos_ref, sin_ref, pmf_ref, loo_ref, *, n: int):
    p = p_ref[...].astype(jnp.float32)                 # (BB, N)
    f = _pmf_body(p, cos_ref[...], sin_ref[...], n + 1, n)
    pmf_ref[...] = f

    # Leave-one-out for all N nodes at once; (BB, S, N) output layout.
    use_fwd = p <= 0.5                                 # (BB, N)
    half = np.float32(0.5)
    q_safe = jnp.where(use_fwd, 1.0 - p, half)         # benign divisors for
    p_safe = jnp.where(use_fwd, half, p)               # the masked-out branch
    zero = jnp.zeros(p.shape, jnp.float32)

    def fwd_step(k, g_prev):
        g_k = (_column(f, k) - p * g_prev) / q_safe
        loo_ref[:, pl.ds(k, 1), :] = g_k[:, None, :]
        return g_k

    _loop(n, fwd_step, zero)
    loo_ref[:, pl.ds(n, 1), :] = zero[:, None, :]      # support is 0..N-1

    def bwd_step(j, g_next):                           # k runs n-1 .. 0
        k = n - 1 - j
        g_k = (_column(f, k + 1) - (1.0 - p) * g_next) / p_safe
        keep = loo_ref[:, pl.ds(k, 1), :][:, 0, :]     # forward-pass value
        loo_ref[:, pl.ds(k, 1), :] = jnp.where(use_fwd, keep, g_k)[:, None, :]
        return g_k

    _loop(n, bwd_step, zero)


@functools.partial(jax.jit,
                   static_argnames=("block_b", "with_loo", "interpret"))
def poibin_dft(p_mat, *, block_b: int = 8, with_loo: bool = True,
               interpret: bool = False):
    """p_mat: (B, N) -> pmf (B, N+1) [, loo (B, N, N+1) if ``with_loo``]."""
    b, n = p_mat.shape
    size = n + 1
    block_b = min(block_b, b)
    n_b = pl.cdiv(b, block_b)
    pad = n_b * block_b - b
    p32 = jnp.pad(p_mat.astype(jnp.float32), ((0, pad), (0, 0)))
    idx = jnp.arange(size)
    ang = 2.0 * jnp.pi * jnp.outer(idx, idx) / size
    cos = jnp.cos(ang).astype(jnp.float32)
    sin = jnp.sin(ang).astype(jnp.float32)

    in_specs = [
        pl.BlockSpec((block_b, n), lambda i: (i, _ZERO)),
        pl.BlockSpec((size, size), lambda i: (_ZERO, _ZERO)),
        pl.BlockSpec((size, size), lambda i: (_ZERO, _ZERO)),
    ]
    if not with_loo:
        pmf = pl.pallas_call(
            functools.partial(_kernel_pmf, n=n),
            grid=(n_b,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block_b, size), lambda i: (i, _ZERO)),
            out_shape=jax.ShapeDtypeStruct((n_b * block_b, size),
                                           jnp.float32),
            compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
            interpret=interpret,
        )(p32, cos, sin)
        return pmf[:b].astype(p_mat.dtype)

    pmf, loo = pl.pallas_call(
        functools.partial(_kernel_loo, n=n),
        grid=(n_b,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_b, size), lambda i: (i, _ZERO)),
            pl.BlockSpec((block_b, size, n), lambda i: (i, _ZERO, _ZERO)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_b * block_b, size), jnp.float32),
            jax.ShapeDtypeStruct((n_b * block_b, size, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(p32, cos, sin)
    return (pmf[:b].astype(p_mat.dtype),
            jnp.swapaxes(loo, 1, 2)[:b].astype(p_mat.dtype))
