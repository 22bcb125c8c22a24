"""Selective-scan (Mamba) Pallas TPU kernel — Hymba's SSM branch.

TPU adaptation: the CUDA kernel parallelizes over channels with one thread
each; here a (Din_tile, N) fp32 state is VMEM-resident and the kernel
consumes (BT,)-length time tiles, vectorizing the diagonal recurrence over
the channel tile on the VPU:

    h_t = exp(Δ_t ⊙ A) ⊙ h_{t-1} + (Δ_t B_t) ⊙ x_t ;   y_t = h_t C_t + D x_t

* grid = (batch, channel_tiles, time_tiles), time innermost/"arbitrary" so
  the state scratch carries.
* Per-tile VMEM: BT·DC (x, Δ) + 2·BT·N (B, C) + DC·N state; DC=512, N=16,
  BT=256 fp32 ≈ 1.3 MB.

Oracle: :func:`repro.kernels.ref.ssm_scan_ref`.
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


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, dskip_ref, y_ref, h_out_ref,
            h_scr, *, block_t: int, n_t_blocks: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)           # (BT, DC)
    dt = dt_ref[0].astype(jnp.float32)         # (BT, DC)
    a = a_ref[...].astype(jnp.float32)         # (DC, N)
    bsel = b_ref[0].astype(jnp.float32)        # (BT, N)
    csel = c_ref[0].astype(jnp.float32)        # (BT, N)
    dskip = dskip_ref[...].astype(jnp.float32)  # (DC,)

    neg_a = -jnp.exp(a)                        # (DC, N)

    def step(t, carry):
        h, ys = carry                           # h: (DC, N)
        xt = jax.lax.dynamic_slice_in_dim(x, t, 1, 0)[0]      # (DC,)
        dtt = jax.lax.dynamic_slice_in_dim(dt, t, 1, 0)[0]    # (DC,)
        bt = jax.lax.dynamic_slice_in_dim(bsel, t, 1, 0)[0]   # (N,)
        ct = jax.lax.dynamic_slice_in_dim(csel, t, 1, 0)[0]   # (N,)
        da = jnp.exp(dtt[:, None] * neg_a)                    # (DC, N)
        h = da * h + (dtt * xt)[:, None] * bt[None, :]
        y = (h * ct[None, :]).sum(axis=1) + dskip * xt        # (DC,)
        ys = jax.lax.dynamic_update_slice_in_dim(ys, y[None], t, 0)
        return h, ys

    h0 = h_scr[...]
    ys0 = jnp.zeros_like(x)
    h, ys = jax.lax.fori_loop(0, block_t, step, (h0, ys0))
    h_scr[...] = h
    y_ref[0] = ys.astype(y_ref.dtype)

    @pl.when(ti == n_t_blocks - 1)
    def write_state():
        h_out_ref[0] = h


@functools.partial(jax.jit, static_argnames=("block_t", "block_d", "interpret"))
def ssm_scan(x, delta, a_log, b, c, d_skip, *, block_t: int = 256,
             block_d: int = 512, interpret: bool = False):
    """x, delta: (B,S,Din); a_log: (Din,N); b,c: (B,S,N); d_skip: (Din,).

    Returns (y (B,S,Din), h_final (B,Din,N) fp32).
    """
    bsz, s, d_in = x.shape
    n = a_log.shape[1]
    block_t = min(block_t, s)
    block_d = min(block_d, d_in)
    n_t = pl.cdiv(s, block_t)
    n_d = pl.cdiv(d_in, block_d)
    pad_t = n_t * block_t - s
    pad_d = n_d * block_d - d_in

    xt = jnp.moveaxis(x, 1, 1)
    if pad_t or pad_d:
        x = jnp.pad(x, ((0, 0), (0, pad_t), (0, pad_d)))
        delta = jnp.pad(delta, ((0, 0), (0, pad_t), (0, pad_d)))
        b = jnp.pad(b, ((0, 0), (0, pad_t), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad_t), (0, 0)))
        a_log = jnp.pad(a_log, ((0, pad_d), (0, 0)))
        d_skip = jnp.pad(d_skip, ((0, pad_d),))

    kernel = functools.partial(_kernel, block_t=block_t, n_t_blocks=n_t)
    y, h = pl.pallas_call(
        kernel,
        grid=(bsz, n_d, n_t),
        in_specs=[
            pl.BlockSpec((1, block_t, block_d),
                         lambda bb, dd, tt: (bb, tt, dd)),
            pl.BlockSpec((1, block_t, block_d),
                         lambda bb, dd, tt: (bb, tt, dd)),
            pl.BlockSpec((block_d, n), lambda bb, dd, tt: (dd, _ZERO)),
            pl.BlockSpec((1, block_t, n), lambda bb, dd, tt: (bb, tt, _ZERO)),
            pl.BlockSpec((1, block_t, n), lambda bb, dd, tt: (bb, tt, _ZERO)),
            pl.BlockSpec((block_d,), lambda bb, dd, tt: (dd,)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, block_d),
                         lambda bb, dd, tt: (bb, tt, dd)),
            pl.BlockSpec((1, block_d, n), lambda bb, dd, tt: (bb, dd, _ZERO)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, n_t * block_t, n_d * block_d), x.dtype),
            jax.ShapeDtypeStruct((bsz, n_d * block_d, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, delta, a_log, b, c, d_skip)
    y = y[:, :s, :d_in]
    return y, h[:, :d_in, :]
