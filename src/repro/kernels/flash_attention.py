"""Flash attention Pallas TPU kernel (causal / sliding-window, GQA).

Streaming-softmax attention with explicit VMEM tiling:

* grid = (batch, q_heads, q_blocks, kv_blocks); the kv axis is the innermost
  ("arbitrary") dimension so the fp32 accumulators in VMEM scratch carry
  across kv iterations of one (b, h, qi) tile.
* BlockSpecs stream (BQ, D) query tiles against (BK, D) key/value tiles —
  per-tile VMEM = BQ·D + 2·BK·D + BQ·BK (+ fp32 accumulators), e.g.
  (128, 128)-tiles with D=128 in bf16: ~0.5 MB, far under the ~16 MB v5e
  VMEM budget.
* MXU alignment: BQ, BK, D are multiples of 128 at production shapes (the
  CPU interpret tests also sweep ragged shapes to exercise the masking).
* Causal skip: kv tiles strictly above the diagonal do zero work via
  @pl.when; the sliding-window skip mirrors it on the stale left edge —
  long-window decode only touches ceil(W/BK) tiles per query tile.
* GQA: kv tiles are indexed by h // (H/KV) so query-head groups share loads.

Oracle: :func:`repro.kernels.ref.flash_attention_ref`.
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
NEG_INF = np.float32(-1e30)
_FZERO = np.float32(0.0)    # f32 even under x64, like _ZERO


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_q: int, block_k: int, seq_len: int,
            causal: bool, window: int, n_kv_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # tile-level skips (traced scalars; zero work when false)
    should = jnp.asarray(True)
    if causal:
        should = jnp.logical_and(should,
                                 k_start <= q_start + block_q - 1)
    if window > 0:
        should = jnp.logical_and(
            should, k_start + block_k - 1 >= q_start - window + 1)

    @pl.when(should)
    def compute():
        q = q_ref[0, 0].astype(jnp.float32)                 # (BQ, D)
        k = k_ref[0, 0].astype(jnp.float32)                 # (BK, D)
        v = v_ref[0, 0].astype(jnp.float32)                 # (BK, D)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (BQ, BK)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
        mask = k_pos < seq_len                               # ragged tail
        if causal:
            mask &= q_pos >= k_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
        scores = jnp.where(mask, scores, NEG_INF)

        m_prev = m_scr[...]                                  # (BQ,)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1))
        # fully-masked-so-far rows keep m == NEG_INF: no correction term
        correction = jnp.where(m_prev == NEG_INF, _FZERO,
                               jnp.exp(m_prev - m_new))
        p = jnp.where(mask, jnp.exp(scores - m_new[:, None]), _FZERO)
        l_scr[...] = l_scr[...] * correction + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * correction[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B,S,H,D); k,v: (B,S,KV,D) -> (B,S,H,D)."""
    b, s, h, d = q.shape
    kvh = k.shape[2]
    groups = h // kvh
    scale = d ** -0.5

    qt = jnp.moveaxis(q, 2, 1)                               # (B,H,S,D)
    kt = jnp.moveaxis(k, 2, 1)                               # (B,KV,S,D)
    vt = jnp.moveaxis(v, 2, 1)

    block_q = min(block_q, s)
    block_k = min(block_k, s)
    n_q = pl.cdiv(s, block_q)
    n_k = pl.cdiv(s, block_k)
    if n_q * block_q != s:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, n_q * block_q - s), (0, 0)))
    if n_k * block_k != s:
        pad = n_k * block_k - s
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))

    def kv_head(hh):                 # hh // groups as int32 (x64 would widen)
        return jax.lax.div(hh, np.int32(groups))

    kernel = functools.partial(
        _kernel, scale=scale, block_q=block_q, block_k=block_k, seq_len=s,
        causal=causal, window=window, n_kv_blocks=n_k)

    out = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bb, hh, qq, kk: (bb, hh, qq, _ZERO)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, hh, qq, kk: (bb, kv_head(hh), kk, _ZERO)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, hh, qq, kk: (bb, kv_head(hh), kk, _ZERO)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bb, hh, qq, kk: (bb, hh, qq, _ZERO)),
        out_shape=jax.ShapeDtypeStruct((b, h, n_q * block_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)

    out = out[:, :, :s, :]
    return jnp.moveaxis(out, 1, 2)                           # (B,S,H,D)
