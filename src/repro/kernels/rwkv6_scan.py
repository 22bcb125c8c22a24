"""WKV6 recurrence Pallas TPU kernel (RWKV-6 data-dependent decay).

TPU adaptation of the CUDA wkv6 kernel: instead of one thread per channel,
the (Dk x Dv) per-head state lives in VMEM scratch as a matrix and each grid
step consumes a (BT, D) time tile, running the recurrence with rank-1
updates formed by VPU outer products:

    out_t = r_t^T (S + diag(u) k_t v_t^T)
    S     = diag(w_t) S + k_t v_t^T

* grid = (batch, heads, time_tiles); the time axis is "arbitrary" so the
  fp32 state scratch carries across tiles.
* Per-tile VMEM: 4·BT·D (r,k,v,w) + D·D state + BT·D out; head_dim 64 and
  BT=256 in fp32 is ~0.5 MB.
* The final state is written to a second output on the last tile (used by
  chunked prefill / decode handoff).

Oracle: :func:`repro.kernels.ref.rwkv6_scan_ref`.
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


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_out_ref, state_scr,
            *, block_t: int, n_t_blocks: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def init():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[0, 0].astype(jnp.float32)        # (BT, D)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)           # (D,)

    def step(t, carry):
        state, out = carry
        rt = jax.lax.dynamic_slice_in_dim(r, t, 1, 0)[0]     # (D,)
        kt = jax.lax.dynamic_slice_in_dim(k, t, 1, 0)[0]
        vt = jax.lax.dynamic_slice_in_dim(v, t, 1, 0)[0]
        wt = jax.lax.dynamic_slice_in_dim(w, t, 1, 0)[0]
        kv = kt[:, None] * vt[None, :]                       # (Dk, Dv)
        y = (rt[:, None] * (state + u[:, None] * kv)).sum(axis=0)
        out = jax.lax.dynamic_update_slice_in_dim(out, y[None], t, 0)
        state = wt[:, None] * state + kv
        return state, out

    state0 = state_scr[...]
    out0 = jnp.zeros((block_t, v.shape[1]), jnp.float32)
    state, out = jax.lax.fori_loop(0, block_t, step, (state0, out0))
    state_scr[...] = state
    o_ref[0, 0] = out.astype(o_ref.dtype)

    @pl.when(ti == n_t_blocks - 1)
    def write_state():
        s_out_ref[0, 0] = state


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def rwkv6_scan(r, k, v, w, u, *, block_t: int = 256, interpret: bool = False):
    """r,k,v,w: (B,S,H,D); u: (H,D) -> (out (B,S,H,D), state (B,H,D,D))."""
    b, s, h, d = r.shape
    block_t = min(block_t, s)
    n_t = pl.cdiv(s, block_t)
    pad = n_t * block_t - s

    def prep(x, pad_value=0.0):
        x = jnp.moveaxis(x, 1, 2)                            # (B,H,S,D)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)),
                        constant_values=pad_value)
        return x

    rt, kt, vt = prep(r), prep(k), prep(v)
    wt = prep(w, pad_value=1.0)   # decay 1.0 on padding leaves state frozen

    kernel = functools.partial(_kernel, block_t=block_t, n_t_blocks=n_t)
    out, state = pl.pallas_call(
        kernel,
        grid=(b, h, n_t),
        in_specs=[
            pl.BlockSpec((1, 1, block_t, d), lambda bb, hh, tt: (bb, hh, tt, _ZERO)),
            pl.BlockSpec((1, 1, block_t, d), lambda bb, hh, tt: (bb, hh, tt, _ZERO)),
            pl.BlockSpec((1, 1, block_t, d), lambda bb, hh, tt: (bb, hh, tt, _ZERO)),
            pl.BlockSpec((1, 1, block_t, d), lambda bb, hh, tt: (bb, hh, tt, _ZERO)),
            pl.BlockSpec((1, d), lambda bb, hh, tt: (hh, _ZERO)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_t, d), lambda bb, hh, tt: (bb, hh, tt, _ZERO)),
            pl.BlockSpec((1, 1, d, d), lambda bb, hh, tt: (bb, hh, _ZERO, _ZERO)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, n_t * block_t, d), r.dtype),
            jax.ShapeDtypeStruct((b, h, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(rt, kt, vt, wt, u)
    out = out[:, :, :s, :]
    return jnp.moveaxis(out, 1, 2), state
