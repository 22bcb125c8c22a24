"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["flash_attention_ref", "rwkv6_scan_ref", "ssm_scan_ref",
           "fedavg_agg_ref", "fused_ce_ref", "poibin_dft_ref"]


def flash_attention_ref(q, k, v, causal: bool = True, window: int = 0,
                        scale: float | None = None):
    """q: (B,S,H,D); k,v: (B,S,KV,D); GQA broadcast; fp32 softmax.

    window > 0 limits attention to the last `window` positions (inclusive of
    self): j in (i-window, i].
    """
    b, s, h, d = q.shape
    kvh = k.shape[2]
    groups = h // kvh
    scale = d ** -0.5 if scale is None else scale
    qg = q.reshape(b, s, kvh, groups, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= i >= j
    if window > 0:
        mask &= (i - j) < window
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return out.reshape(b, s, h, d)


def rwkv6_scan_ref(r, k, v, w, u, state=None):
    """Sequential WKV6 (same math as models.rwkv.wkv_scan).

    r,k,v,w: (B,S,H,D); u: (H,D); state: (B,H,D,D) or None.
    Returns (out (B,S,H,D), final_state fp32).

    ``state`` is an oracle-only convenience for chunked-scan tests: the
    Pallas kernel (and the ``ops.rwkv6`` wrapper) always starts from the
    zero state and returns the final state for the caller to chain.
    """
    b, s, h, d = r.shape
    if state is None:
        state = jnp.zeros((b, h, d, d), jnp.float32)
    rf, kf, vf, wf = (t.astype(jnp.float32) for t in (r, k, v, w))
    uf = u.astype(jnp.float32)

    def step(st, rkvw):
        rt, kt, vt, wt = rkvw
        kv = kt[..., :, None] * vt[..., None, :]
        out = jnp.einsum("bhk,bhkv->bhv", rt, st + uf[..., None] * kv)
        st = wt[..., :, None] * st + kv
        return st, out

    rs, ks, vs, ws = (jnp.moveaxis(t, 1, 0) for t in (rf, kf, vf, wf))
    state, outs = jax.lax.scan(step, state, (rs, ks, vs, ws))
    return jnp.moveaxis(outs, 0, 1).astype(r.dtype), state


def ssm_scan_ref(x, delta, a_log, b, c, d_skip, h0=None):
    """Mamba selective scan (same math as models.ssm.selective_scan).

    x, delta: (B,S,Din); a_log: (Din,N); b,c: (B,S,N); d_skip: (Din,);
    h0: (B,Din,N) or None. Returns (y (B,S,Din), h_final fp32).

    Like ``rwkv6_scan_ref``, ``h0`` is oracle-only: the Pallas kernel and
    the ``ops.ssm`` wrapper always start from the zero state.
    """
    bsz, s, d_in = x.shape
    n = a_log.shape[1]
    if h0 is None:
        h0 = jnp.zeros((bsz, d_in, n), jnp.float32)
    xf = x.astype(jnp.float32)
    df = delta.astype(jnp.float32)
    da = jnp.exp(df[..., None] * (-jnp.exp(a_log))[None, None])
    dbx = df[..., None] * b.astype(jnp.float32)[:, :, None, :] * xf[..., None]

    def step(h, inp):
        da_t, dbx_t, c_t = inp
        h = da_t * h + dbx_t
        return h, jnp.einsum("bdn,bn->bd", h, c_t)

    h, ys = jax.lax.scan(step, h0.astype(jnp.float32),
                         (jnp.moveaxis(da, 1, 0), jnp.moveaxis(dbx, 1, 0),
                          jnp.moveaxis(c.astype(jnp.float32), 1, 0)))
    y = jnp.moveaxis(ys, 0, 1) + xf * d_skip[None, None]
    return y.astype(x.dtype), h


def fedavg_agg_ref(global_flat, client_flat, mask):
    """Masked mean over the client axis with k=0 fallback.

    global_flat: (P,); client_flat: (N,P); mask: (N,) bool 0/1
    participation, or float participation·weight products (the weighted
    FedAvg path — the math is the same Σmθ/Σm). fp32 accumulation; output
    in ``global_flat.dtype``.
    """
    m = mask.astype(jnp.float32)
    total = jnp.sum(m)
    avg = jnp.einsum("np,n->p", client_flat.astype(jnp.float32), m) \
        / jnp.maximum(total, 1e-9)
    return jnp.where(total > 0, avg,
                     global_flat.astype(jnp.float32)).astype(global_flat.dtype)


@functools.partial(jax.jit, static_argnames=("with_loo",))
def poibin_dft_ref(p_mat, with_loo: bool = True):
    """Batched Poisson-Binomial DFT pmf + leave-one-out deconvolution.

    p_mat: (B, N) probabilities in [0, 1]. Returns pmf (B, N+1) and — with
    ``with_loo`` — loo (B, N, N+1) where ``loo[b, i]`` is the pmf of
    scenario b's nodes excluding node i (support 0..N-1, last entry zero).

    Same math as :func:`repro.core.poibin.poibin_pmf` (eq. (9) DFT in real
    (re, im) arithmetic, with clip + renormalize) and
    :func:`repro.core.poibin.poibin_pmf_loo` (forward recursion for
    p ≤ 1/2, backward for p > 1/2), restated here self-contained in the
    input dtype so the kernel layer stays dependency-free; the three-way
    agreement (this oracle, the Pallas kernel, the repro.core functions) is
    pinned in ``tests/test_property_poibin.py``.
    """
    p_mat = jnp.asarray(p_mat)
    _, n = p_mat.shape
    size = n + 1
    dtype = p_mat.dtype
    idx = jnp.arange(size)
    ang = 2 * jnp.pi * idx / size
    w_re, w_im = jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)

    def factor(chi, p_k):                                      # p_k: (B,)
        re, im = chi
        t_re = p_k[:, None] * (w_re - 1.0) + 1.0
        t_im = p_k[:, None] * w_im
        return (re * t_re - im * t_im, re * t_im + im * t_re), None

    one = jnp.ones((p_mat.shape[0], size), dtype)
    (chi_re, chi_im), _ = jax.lax.scan(factor, (one, jnp.zeros_like(one)),
                                       p_mat.T)                # (B, S) each
    theta = 2 * jnp.pi * jnp.outer(idx, idx) / size
    cos, sin = jnp.cos(theta).astype(dtype), jnp.sin(theta).astype(dtype)
    raw = jnp.sum(cos * chi_re[:, None, :] + sin * chi_im[:, None, :],
                  axis=2) / size
    raw = jnp.clip(raw, 0.0, 1.0)
    pmf = raw / jnp.sum(raw, axis=1, keepdims=True)
    if not with_loo:
        return pmf

    def loo_one(f, p_i):
        q_i = 1.0 - p_i
        use_fwd = p_i <= 0.5
        q_safe = jnp.where(use_fwd, q_i, 0.5)
        p_safe = jnp.where(use_fwd, 0.5, p_i)

        def fwd(g_prev, f_k):
            g_k = (f_k - p_i * g_prev) / q_safe
            return g_k, g_k

        _, g_fwd = jax.lax.scan(fwd, jnp.zeros((), f.dtype), f[:-1])

        def bwd(g_next, f_k1):
            g_k = (f_k1 - q_i * g_next) / p_safe
            return g_k, g_k

        _, g_bwd = jax.lax.scan(bwd, jnp.zeros((), f.dtype), f[1:],
                                reverse=True)
        g = jnp.where(use_fwd, g_fwd, g_bwd)
        return jnp.concatenate([g, jnp.zeros((1,), f.dtype)])

    loo = jax.vmap(jax.vmap(loo_one, in_axes=(None, 0)))(pmf, p_mat)
    return pmf, loo


def fused_ce_ref(hidden, w_vocab, labels):
    """Per-token NLL via dense logits (the memory hog the kernel avoids)."""
    logits = (hidden.astype(jnp.float32) @ w_vocab.astype(jnp.float32))
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    lab = jnp.take_along_axis(logits, labels[:, None].astype(jnp.int32),
                              axis=1)[:, 0]
    return lse - lab
