"""ResNet-18 in pure JAX — the paper's federated workload (CIFAR-10).

11,173,962 parameters at width 64 and 10 classes with the CIFAR 3×3
stem. Table I's w = 11 181 642 (S_w = 44.73 MB fp32) is the same network
with torchvision's 7×7 stem, 7,680 more weights. Norm layer is configurable:
``groupnorm`` (default — BN running stats are notoriously ill-posed under
FedAvg) or ``batchnorm`` (paper-faithful; stats are FedAvg-merged like any
other parameter). See DESIGN.md §9.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

Params = dict

STAGES = (64, 128, 256, 512)
BLOCKS_PER_STAGE = 2


def _conv_init(key, k, c_in, c_out):
    fan_in = k * k * c_in
    w = jax.random.truncated_normal(key, -2, 2, (k, k, c_in, c_out),
                                    jnp.float32)
    return w * (2.0 / fan_in) ** 0.5


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _norm_params(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def _apply_norm(p, x, kind: str, groups: int = 8):
    if kind == "groupnorm":
        b, h, w, c = x.shape
        g = min(groups, c)
        xg = x.reshape(b, h, w, g, c // g)
        mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
        var = jnp.var(xg, axis=(1, 2, 4), keepdims=True)
        xg = (xg - mean) * jax.lax.rsqrt(var + 1e-5)
        x = xg.reshape(b, h, w, c)
    else:  # batchnorm (batch statistics; stats FedAvg'd with the params)
        mean = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
        var = jnp.var(x, axis=(0, 1, 2), keepdims=True)
        x = (x - mean) * jax.lax.rsqrt(var + 1e-5)
    return x * p["scale"] + p["bias"]


def init_resnet18(key, n_classes: int = 10, width: int = 64):
    ks = jax.random.split(key, 64)
    ki = iter(range(64))
    p: Params = {}
    p["stem_conv"] = _conv_init(ks[next(ki)], 3, 3, width)
    p["stem_norm"] = _norm_params(width)
    c_in = width
    for si, mult in enumerate((1, 2, 4, 8)):
        c_out = width * mult
        for bi in range(BLOCKS_PER_STAGE):
            stride = 2 if (si > 0 and bi == 0) else 1
            blk = {
                "conv1": _conv_init(ks[next(ki)], 3, c_in, c_out),
                "norm1": _norm_params(c_out),
                "conv2": _conv_init(ks[next(ki)], 3, c_out, c_out),
                "norm2": _norm_params(c_out),
            }
            if stride != 1 or c_in != c_out:
                blk["proj"] = _conv_init(ks[next(ki)], 1, c_in, c_out)
                blk["proj_norm"] = _norm_params(c_out)
            p[f"stage{si}_block{bi}"] = blk
            c_in = c_out
    p["head_w"] = jax.random.truncated_normal(
        ks[next(ki)], -2, 2, (c_in, n_classes), jnp.float32) * c_in**-0.5
    p["head_b"] = jnp.zeros((n_classes,), jnp.float32)
    return p


def _block_apply(p, x, stride, norm_kind):
    y = _conv(x, p["conv1"], stride)
    y = jax.nn.relu(_apply_norm(p["norm1"], y, norm_kind))
    y = _conv(y, p["conv2"], 1)
    y = _apply_norm(p["norm2"], y, norm_kind)
    if "proj" in p:
        x = _apply_norm(p["proj_norm"], _conv(x, p["proj"], stride), norm_kind)
    return jax.nn.relu(x + y)


def forward(params: Params, images: jax.Array,
            norm: Literal["groupnorm", "batchnorm"] = "groupnorm"):
    """images: (B, 32, 32, 3) float32 -> logits (B, n_classes)."""
    x = _conv(images, params["stem_conv"], 1)
    x = jax.nn.relu(_apply_norm(params["stem_norm"], x, norm))
    for si in range(4):
        for bi in range(BLOCKS_PER_STAGE):
            stride = 2 if (si > 0 and bi == 0) else 1
            x = _block_apply(params[f"stage{si}_block{bi}"], x, stride, norm)
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["head_w"] + params["head_b"]


def loss_fn(params: Params, batch: dict, norm="groupnorm"):
    logits = forward(params, batch["images"], norm)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1)[:, 0]
    return jnp.mean(nll)


def accuracy(params: Params, batch: dict, norm="groupnorm"):
    logits = forward(params, batch["images"], norm)
    return jnp.mean(jnp.argmax(logits, -1) == batch["labels"])


def param_count(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))
