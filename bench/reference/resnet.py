"""Plain reference of a FedAvg campaign of CIFAR-stem ResNet-18 clients.

Written from the published description (He et al. 2015, with GroupNorm
of 8 groups in place of BatchNorm and "SAME" padding) and the paper's
FedAvg round: each round every node joins with its probability, the
nodes that joined take ``local_steps`` SGD steps from the global model on
their own batch, and the global model becomes their mean (unchanged when
nobody joined). A campaign stops counting once validation accuracy has
met the target for ``consecutive`` rounds. It imports nothing of the
system under test; the weights and data come from ``bench.inputs``.

Departures from the program: one client at a time, only the clients that
joined are trained, and the mean is a running sum. ``precision`` and
``dtype`` select the reference (``HIGHEST``, float32) or the control (the
nearest lower precision, bfloat16 weights, activations and updates).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# RNG stream offsets of the campaign's masks and initial weights: a
# scenario's key is PRNGKey(seed); fold 1 draws its initial weights and
# fold MASK_STREAM + r the participation of round r.
INIT_FOLD = 1
MASK_STREAM = 10_000


def _conv(x, w, stride, precision):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def _group_norm(p, x, groups, eps):
    b, h, w, c = x.shape
    g = min(groups, c)
    xg = x.reshape(b, h, w, g, c // g).astype(jnp.float32)
    mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=(1, 2, 4), keepdims=True)
    y = ((xg - mean) / jnp.sqrt(var + eps)).reshape(b, h, w, c)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


def forward(model: dict, params: dict, images, precision, dtype):
    gn = functools.partial(_group_norm, groups=model["norm_groups"],
                           eps=model["norm_eps"])
    x = _conv(images.astype(dtype), params["stem_conv"], 1, precision)
    x = jax.nn.relu(gn(params["stem_norm"], x))
    for si in range(len(model["stage_widths"])):
        for bi in range(model["blocks_per_stage"]):
            p = params[f"stage{si}_block{bi}"]
            stride = 2 if (si > 0 and bi == 0) else 1
            y = jax.nn.relu(gn(p["norm1"], _conv(x, p["conv1"], stride,
                                                 precision)))
            y = gn(p["norm2"], _conv(y, p["conv2"], 1, precision))
            if "proj" in p:
                x = gn(p["proj_norm"], _conv(x, p["proj"], stride, precision))
            x = jax.nn.relu(x + y)
    feat = jnp.mean(x.astype(jnp.float32), axis=(1, 2)).astype(dtype)
    logits = jnp.matmul(feat, params["head_w"].astype(dtype),
                        precision=precision)
    return logits.astype(jnp.float32) + params["head_b"].astype(jnp.float32)


def loss(model, params, batch, precision, dtype):
    logits = forward(model, params, batch["images"], precision, dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, batch["labels"][:, None],
                                         axis=-1))


class Campaign:
    """Jitted pieces of the reference campaign for one configuration."""

    def __init__(self, cfg: dict, data, init, *, precision="highest",
                 dtype=jnp.float32, half_batch: bool = False):
        self.cfg = cfg
        self.data = data
        self.init = init
        self.dtype = dtype
        model = cfg["model"]
        prec = {"highest": jax.lax.Precision.HIGHEST,
                "default": jax.lax.Precision.DEFAULT}[precision]
        lr = cfg["lr"]
        n_img = cfg["batch_per_client"]
        keep = n_img // 2 if half_batch else n_img

        def client_step(params, cid, rnd):
            batches = data.client_data(cid, rnd, n_img, cfg["local_steps"])
            for s in range(cfg["local_steps"]):
                batch = jax.tree.map(lambda x: x[s, :keep], batches)
                g = jax.grad(loss, argnums=1)(model, params, batch, prec,
                                              dtype)
                params = jax.tree.map(
                    lambda w, gw: (w.astype(jnp.float32)
                                   - lr * gw.astype(jnp.float32)
                                   ).astype(dtype), params, g)
            return params

        def accuracy(params, val):
            logits = forward(model, params, val["images"], prec, dtype)
            return jnp.mean(jnp.argmax(logits, -1) == val["labels"])

        self.client_step = jax.jit(client_step)
        self.accuracy = jax.jit(accuracy)
        self.add = jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: x + y.astype(jnp.float32), a, b))
        self.mean = jax.jit(lambda s, k: jax.tree.map(
            lambda x: (x / k).astype(dtype), s))
        self.f32 = jax.jit(lambda t: jax.tree.map(
            lambda x: x.astype(jnp.float32), t))
        self.init_params = jax.jit(lambda key: jax.tree.map(
            lambda x: x.astype(dtype), init(key)))

    def masks(self, p_row, seed) -> np.ndarray:
        """``(rounds, N)`` participation draws of one scenario."""
        key = jax.random.PRNGKey(jnp.uint32(seed))
        p = jnp.asarray(p_row)
        return np.stack([np.asarray(jax.random.bernoulli(
            jax.random.fold_in(key, MASK_STREAM + r), p, p.shape))
            for r in range(self.cfg["max_rounds"])])

    def run(self, p_row, seed, val) -> dict:
        """One scenario: final weights (float32), participants per round,
        per-node joules, and the initial weights."""
        cfg = self.cfg
        key = jax.random.PRNGKey(jnp.uint32(seed))
        params = self.init_params(jax.random.fold_in(key, INIT_FOLD))
        init = params
        masks = self.masks(p_row, seed)
        ledger = np.zeros(masks.shape[1])
        ks, streak, converged = [], 0, False
        for r, mask in enumerate(masks):
            if converged:
                ks.append(0)
                continue
            joined = np.flatnonzero(mask)
            merged = params
            if joined.size:
                total = None
                for c in joined:
                    new = self.client_step(params, jnp.int32(c),
                                           jnp.int32(r))
                    total = self.f32(new) if total is None \
                        else self.add(total, new)
                merged = self.mean(total, jnp.float32(joined.size))
            acc = float(self.accuracy(merged, val))
            params = merged
            ledger += np.where(mask, cfg["e_participant_j"], cfg["e_idle_j"])
            ks.append(int(joined.size))
            streak = streak + 1 if acc >= np.float32(cfg["target_acc"]) else 0
            converged = streak >= cfg["consecutive"]
        return {"params": self.f32(params), "init": self.f32(init),
                "ks": np.asarray(ks), "ledger_j": ledger}


def leaf_norms(tree) -> list[float]:
    """Frobenius norm of every leaf, float64 on the host."""
    return [float(np.linalg.norm(np.asarray(x, np.float64)))
            for x in jax.tree.leaves(tree)]


def change_gap(prog_params, ref: dict) -> float:
    """Worst leaf's gap between the norm of the program's change of the
    weights and the reference's, over the larger of that leaf's reference
    change and the median leaf's."""
    sub = jax.jit(lambda a, b: jax.tree.map(lambda x, y: x - y, a, b))
    prog = leaf_norms(sub(prog_params, ref["init"]))
    want = leaf_norms(sub(ref["params"], ref["init"]))
    floor = float(np.median(want))
    return max(abs(a - b) / max(b, floor) for a, b in zip(prog, want))
