"""Cluster-mode FedAvg: clients are data-parallel shard groups (shard_map).

In datacenter FL (DESIGN.md §3) each client is one shard group along the
``data`` (and ``pod``) mesh axes. Each group computes its local update from
its private shard; the merge is a participation-masked ``psum`` over those
axes — the paper's eq.-FedAvg with Bernoulli participation, expressed as an
explicit collective so the roofline's collective term *is* the paper's
merge cost.

``fedavg_allreduce_merge`` is written with ``jax.shard_map``: per-device
code sees its own *block* of client updates (the stacked leading client
axis splits over the mesh axes, so large fleets place ``n_clients /
n_devices`` clients per device) plus that block's slice of the mask, and
participates in two psums (masked sum + participant count). Accumulation
runs in ``promote_types(leaf_dtype, float32)`` — f64 leaves merge at full
f64 precision (the campaign layer's mixed f64/bf16 contract), bf16 leaves
still accumulate in f32.

``make_cluster_round`` carries one optimizer state per client (stacked
leading client axis, see :func:`init_cluster_opt_state`) across rounds —
momentum/Adam moments persist round to round exactly like a sequential
per-client loop (pinned in ``tests/test_distributed.py``).
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.optim.base import apply_updates

__all__ = ["fedavg_allreduce_merge", "init_cluster_opt_state",
           "make_cluster_round"]


def fedavg_allreduce_merge(global_params, local_update, mask_local,
                           mesh: Mesh, axes: Sequence[str] = ("data",)):
    """Masked FedAvg across mesh axes via shard_map + psum.

    Args:
        global_params: replicated pytree (previous global model).
        local_update: pytree with the same structure plus a leading client
            axis of size ``n_clients``; it splits over ``axes``, so each
            device holds a contiguous block of ``n_clients / n_devices``
            clients' proposed params (``n_clients`` must divide evenly).
        mask_local: (n_clients,) bool — participation of each client.
        mesh / axes: the device mesh and the axes the client dim spans.

    Returns:
        merged params, replicated (identical on every device). Each leaf
        accumulates in ``promote_types(leaf_dtype, float32)`` — f64 stays
        f64 end to end — and is cast back to the leaf dtype.
    """
    n_devices = 1
    for a in axes:
        n_devices *= mesh.shape[a]
    n_clients = jax.tree.leaves(mask_local)[0].shape[0]
    if n_clients % n_devices != 0:
        raise ValueError(
            f"{n_clients} clients over {n_devices} devices along {axes}: "
            "the client axis must split evenly")
    per = n_clients // n_devices

    def merge_fn(g, upd, mask):
        # per-device view: upd leaves carry this device's block of `per`
        # clients; the mask is replicated, so slice this block's entries.
        idx = jax.lax.axis_index(axes[0])
        if len(axes) > 1:
            for a in axes[1:]:
                idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        m_block = jax.lax.dynamic_slice_in_dim(mask, idx * per, per)
        total = jax.lax.psum(jnp.sum(m_block.astype(jnp.float32)), axes)

        def one(g_leaf, u_leaf):
            acc = jnp.promote_types(g_leaf.dtype, jnp.float32)
            m = m_block.astype(acc).reshape(
                (per,) + (1,) * (u_leaf.ndim - 1))
            contrib = jnp.sum(u_leaf.astype(acc) * m, axis=0)
            s = jax.lax.psum(contrib, axes)
            avg = s / jnp.maximum(total.astype(acc), 1e-9)
            return jnp.where(total > 0, avg,
                             g_leaf.astype(acc)).astype(g_leaf.dtype)

        return jax.tree.map(one, g, upd)

    client_spec = P(tuple(axes))
    in_specs = (
        jax.tree.map(lambda _: P(), global_params),
        jax.tree.map(lambda _: client_spec, local_update),
        P(),
    )
    out_specs = jax.tree.map(lambda _: P(), global_params)
    # Replication checking off: the merge psums by hand.
    fn = jax.shard_map(merge_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(global_params, local_update, mask_local)


def init_cluster_opt_state(opt, params, n_clients: int):
    """Per-client optimizer states, stacked along a leading client axis.

    The stacked pytree feeds :func:`make_cluster_round`'s ``opt_state``
    argument (and round outputs thread straight back in), so every client
    keeps its own Adam/momentum moments across rounds.
    """
    return jax.vmap(lambda _: opt.init(params))(jnp.arange(n_clients))


def make_cluster_round(loss_fn, opt, mesh: Mesh, axes=("data",)):
    """One cluster FL round: local step per shard group + masked merge.

    Returns ``round_fn(params, opt_state, batch, mask) -> (merged,
    opt_state, losses)``, jittable under ``mesh``: ``opt_state`` and the
    ``batch`` leaves carry a leading client dim (sharded over ``axes``;
    build the initial state with :func:`init_cluster_opt_state`). The
    returned ``opt_state`` is each client's *advanced* state — thread it
    into the next round so optimizer moments accumulate across rounds
    instead of resetting (the seed version re-``init``-ed per round and
    dropped the update, silently degrading Adam to sign-less SGD).
    """
    def round_fn(params, opt_state, batch, mask):
        def local(p, st, b):
            loss, grads = jax.value_and_grad(loss_fn)(p, b)
            updates, new_st = opt.update(grads, st, p)
            return apply_updates(p, updates), new_st, loss

        client_params, new_state, losses = jax.vmap(
            local, in_axes=(None, 0, 0))(params, opt_state, batch)
        merged = fedavg_allreduce_merge(params, client_params, mask, mesh,
                                        axes)
        return merged, new_state, losses

    return round_fn
