"""Inputs the benchmark makes: weights and data, from seeds, on the device.

The program under test and the plain reference both take these; neither
makes its own. The parameter tree has the layout the program's ResNet-18
reads (``stem_conv``, ``stage{s}_block{b}``, ``head_w``, ...).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def resnet18_init(model: dict, key: jax.Array) -> dict:
    """He-normal convolutions (truncated at 2 sigma), unit norm scales,
    zero biases, a 1/sqrt(fan-in) head; float32."""
    widths = model["stage_widths"]
    counter = iter(range(1 << 10))

    def conv(k, c_in, c_out):
        w = jax.random.truncated_normal(
            jax.random.fold_in(key, next(counter)), -2.0, 2.0,
            (k, k, c_in, c_out), jnp.float32)
        return w * jnp.float32((2.0 / (k * k * c_in)) ** 0.5)

    def norm(c):
        return {"scale": jnp.ones((c,), jnp.float32),
                "bias": jnp.zeros((c,), jnp.float32)}

    c_img = model["image_shape"][2]
    p = {"stem_conv": conv(model["stem_kernel"], c_img, widths[0]),
         "stem_norm": norm(widths[0])}
    c_in = widths[0]
    for si, c_out in enumerate(widths):
        for bi in range(model["blocks_per_stage"]):
            stride = 2 if (si > 0 and bi == 0) else 1
            blk = {"conv1": conv(3, c_in, c_out), "norm1": norm(c_out),
                   "conv2": conv(3, c_out, c_out), "norm2": norm(c_out)}
            if stride != 1 or c_in != c_out:
                blk["proj"] = conv(1, c_in, c_out)
                blk["proj_norm"] = norm(c_out)
            p[f"stage{si}_block{bi}"] = blk
            c_in = c_out
    n_cls = model["n_classes"]
    p["head_w"] = jax.random.truncated_normal(
        jax.random.fold_in(key, next(counter)), -2.0, 2.0, (c_in, n_cls),
        jnp.float32) * jnp.float32(c_in ** -0.5)
    p["head_b"] = jnp.zeros((n_cls,), jnp.float32)
    return p


class TemplateNoise:
    """CIFAR-shaped images: a class template plus Gaussian noise.

    The generator of ``repro.data.synthetic.SyntheticCifar``, copied: the
    templates come from ``data_seed``, and a client's batch in a round from
    ``fold_in(fold_in(PRNGKey(data_seed), client), round)``, so the stream
    is a fixed dataset that every campaign call shares.
    """

    def __init__(self, data: dict, model: dict):
        self.seed = int(data["data_seed"])
        self.noise = float(data["noise"])
        self.shape = tuple(model["image_shape"])
        self.n_classes = int(model["n_classes"])

    def batch(self, key: jax.Array, n: int) -> dict:
        k_lab, k_noise = jax.random.split(key)
        templates = jax.random.normal(jax.random.PRNGKey(self.seed),
                                      (self.n_classes, *self.shape),
                                      jnp.float32)
        labels = jax.random.randint(k_lab, (n,), 0, self.n_classes,
                                    jnp.int32)
        noise = jax.random.normal(k_noise, (n, *self.shape), jnp.float32)
        return {"images": templates[labels] + noise * jnp.float32(self.noise),
                "labels": labels}

    def client_data(self, cid, rnd, n: int, steps: int) -> dict:
        """``(steps, n, ...)`` batches of one client in one round."""
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(self.seed), cid), rnd)
        return jax.vmap(lambda k: self.batch(k, n))(
            jax.random.split(key, steps))

    def val_batch(self, n: int) -> dict:
        return jax.jit(self.batch, static_argnums=1)(
            jax.random.PRNGKey(self.seed + 10_007), n)


def ne_fleets(key: jax.Array, batch: int, n: int, costs: list, gammas: list,
              dtype=jnp.float64) -> tuple[jax.Array, jax.Array]:
    """``(batch, n)`` per-node costs and AoI weights, uniform on the
    configuration's ranges."""
    kc, kg = jax.random.split(key)
    c = jax.random.uniform(kc, (batch, n), dtype, costs[0], costs[1])
    g = jax.random.uniform(kg, (batch, n), dtype, gammas[0], gammas[1])
    return c, g
