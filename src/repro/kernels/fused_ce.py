"""Fused cross-entropy Pallas TPU kernel (hidden @ vocab -> per-token NLL).

For 200k-class vocabularies (phi4, gemma) the logits tensor (T, V) is the
single largest activation in the training step — bigger than the attention
scores at train_4k. This kernel never materializes it in HBM: vocab tiles
stream through VMEM with an online logsumexp, and the label logit is
accumulated on the fly:

    nll_t = logsumexp_v(h_t · W_v) − h_t · W_{label_t}

* grid = (token_tiles, vocab_tiles); vocab is the innermost "arbitrary"
  dimension so the fp32 running (m, l, label_logit) scratch carries.
* Per-tile VMEM: BT·D (hidden) + D·BV (weight tile) + BT·BV (logit tile);
  (128 tokens × 512 vocab × D=4096) bf16 ≈ 4.5 MB.
* labels enter as an (BT,) int tile; the label logit is extracted with a
  one-hot mask inside the tile that owns it.

Oracle: :func:`repro.kernels.ref.fused_ce_ref`.
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


def _kernel(h_ref, w_ref, lab_ref, o_ref, m_scr, l_scr, lab_scr, *,
            block_v: int, vocab: int, n_v_blocks: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        lab_scr[...] = jnp.zeros_like(lab_scr)

    h = h_ref[...].astype(jnp.float32)            # (BT, D)
    w = w_ref[...].astype(jnp.float32)            # (D, BV)
    logits = jax.lax.dot_general(
        h, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)       # (BT, BV)

    v_start = vi * block_v
    v_pos = v_start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    valid = v_pos < vocab
    logits = jnp.where(valid, logits, NEG_INF)

    # online logsumexp
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1))
    corr = jnp.where(m_prev == NEG_INF, _FZERO, jnp.exp(m_prev - m_new))
    p = jnp.where(valid, jnp.exp(logits - m_new[:, None]), _FZERO)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
    m_scr[...] = m_new

    # label logit if it lives in this tile
    lab = lab_ref[...]                            # (BT,)
    hit = (v_pos == lab[:, None]) & valid
    lab_scr[...] = lab_scr[...] + jnp.sum(
        jnp.where(hit, logits, _FZERO), axis=1)

    @pl.when(vi == n_v_blocks - 1)
    def finalize():
        lse = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))
        o_ref[...] = (lse - lab_scr[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "block_v",
                                             "interpret"))
def fused_ce(hidden, w_vocab, labels, *, block_t: int = 128,
             block_v: int = 512, interpret: bool = False):
    """hidden: (T, D); w_vocab: (D, V); labels: (T,) int32 -> (T,) fp32 NLL."""
    t, d = hidden.shape
    v = w_vocab.shape[1]
    block_t = min(block_t, t)
    block_v = min(block_v, v)
    n_t = pl.cdiv(t, block_t)
    n_v = pl.cdiv(v, block_v)
    pad_t = n_t * block_t - t
    pad_v = n_v * block_v - v
    if pad_t:
        hidden = jnp.pad(hidden, ((0, pad_t), (0, 0)))
        labels = jnp.pad(labels, ((0, pad_t),))
    if pad_v:
        w_vocab = jnp.pad(w_vocab, ((0, 0), (0, pad_v)))

    kernel = functools.partial(_kernel, block_v=block_v, vocab=v,
                               n_v_blocks=n_v)
    out = pl.pallas_call(
        kernel,
        grid=(n_t, n_v),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda ti, vi: (ti, _ZERO)),
            pl.BlockSpec((d, block_v), lambda ti, vi: (_ZERO, vi)),
            pl.BlockSpec((block_t,), lambda ti, vi: (ti,)),
        ],
        out_specs=pl.BlockSpec((block_t,), lambda ti, vi: (ti,)),
        out_shape=jax.ShapeDtypeStruct((n_t * block_t,), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_t,), jnp.float32),
            pltpu.VMEM((block_t,), jnp.float32),
            pltpu.VMEM((block_t,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(hidden, w_vocab, labels.astype(jnp.int32))
    return out[:t]
