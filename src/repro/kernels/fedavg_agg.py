"""Participation-masked FedAvg merge Pallas TPU kernel (the paper's agg step).

The server merge is bandwidth-bound elementwise work over the flattened
parameter vector: out = Σ_i m_i θ_i / Σ_i m_i, falling back to the previous
global θ when nobody participated. Fusing mask-multiply + reduce + renorm +
fallback into one pass reads each client parameter exactly once.

* The flat parameter vector is laid out lane-dense as (P/128, 128) rows,
  so every block's last two dims are (BP/128, 128) sublanes × lanes —
  also when the campaign vmaps the kernel over its scenario batch, which
  prepends a squeezed batch dim to each block.
* grid = (param_tiles,); each tile loads an (N, BP/128, 128) client slab
  + the (BP/128, 128) previous-global slice. N ≤ ~64 clients and
  BP = 2048 fp32 keeps tiles ~0.5 MB in VMEM. On the chip BP must be a
  multiple of 1024 (8 sublanes × 128 lanes) or cover all of P.
* The mask lives in SMEM-friendly (N, 1) layout; participant count is
  reduced in-kernel (N is tiny). Float masks carry participation·weight
  products for the weighted-FedAvg path.
* Ragged P is padded up to a ``block_p`` multiple in the wrapper and
  sliced back off; N = 1 degenerates to a copy-or-fallback and the
  all-zero mask returns the previous global exactly.
* dtype policy: fp32 accumulate regardless of input dtype; output in
  ``global_flat.dtype`` (f64 campaign params round-trip through fp32 —
  the pallas backend is parity-to-tolerance, not bitwise).

Oracle: :func:`repro.kernels.ref.fedavg_agg_ref`.
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
_LANES = 128


def _kernel(global_ref, clients_ref, mask_ref, o_ref):
    g = global_ref[...].astype(jnp.float32)          # (BR, 128)
    c = clients_ref[...].astype(jnp.float32)         # (N, BR, 128)
    m = mask_ref[...].astype(jnp.float32)            # (N, 1)
    total = jnp.sum(m)
    avg = jnp.sum(c * m[:, :, None], axis=0) / jnp.maximum(total, 1e-9)
    o_ref[...] = jnp.where(total > 0, avg, g).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def fedavg_agg(global_flat, client_flat, mask, *, block_p: int = 2048,
               interpret: bool = False):
    """global_flat: (P,); client_flat: (N,P); mask: (N,) -> (P,)."""
    n, p = client_flat.shape
    rows = pl.cdiv(p, _LANES)
    block_rows = min(max(block_p // _LANES, 1), rows)
    n_p = pl.cdiv(rows, block_rows)
    pad = n_p * block_rows * _LANES - p
    if pad:
        global_flat = jnp.pad(global_flat, ((0, pad),))
        client_flat = jnp.pad(client_flat, ((0, 0), (0, pad)))
    g = global_flat.reshape(n_p * block_rows, _LANES)
    c = client_flat.reshape(n, n_p * block_rows, _LANES)
    mask2 = mask.astype(jnp.float32).reshape(n, 1)

    out = pl.pallas_call(
        _kernel,
        grid=(n_p,),
        in_specs=[
            pl.BlockSpec((block_rows, _LANES), lambda i: (i, _ZERO)),
            pl.BlockSpec((n, block_rows, _LANES),
                         lambda i: (_ZERO, i, _ZERO)),
            pl.BlockSpec((n, 1), lambda i: (_ZERO, _ZERO)),
        ],
        out_specs=pl.BlockSpec((block_rows, _LANES), lambda i: (i, _ZERO)),
        out_shape=jax.ShapeDtypeStruct(g.shape, global_flat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(g, c, mask2)
    return out.reshape(-1)[:p]
