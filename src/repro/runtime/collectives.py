"""Coflow-scheduled collectives: execute a core.fabric.SlotPlan inside a
training step.

XLA orders collectives by data dependency, so the slot plan is enforced
with jax.lax.optimization_barrier between slot groups: every bucket in
slot t+1 depends on all buckets of slot t having completed.  Within a
slot, a bucket's bytes are split across the ICI axes it was granted
(axis share -> psum over that named axis inside shard_map).

This is the runtime half of the paper's scheduler (core/fabric.py emits
the plan); see DESIGN.md §2.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.fabric import SlotPlan

PyTree = Any


def bucketize(leaves: Sequence[jax.Array], bucket_bytes: float):
    """Group leaves into buckets of ~bucket_bytes (backward order)."""
    buckets, cur, size = [], [], 0.0
    for i, l in enumerate(reversed(leaves)):
        cur.append(len(leaves) - 1 - i)
        size += l.size * l.dtype.itemsize
        if size >= bucket_bytes:
            buckets.append(cur)
            cur, size = [], 0.0
    if cur:
        buckets.append(cur)
    return buckets


def scheduled_psum(leaves: list, bucket_ids: list[list[int]],
                   plan: SlotPlan, axis_names: Sequence[str],
                   dp_axes: Sequence[str]):
    """All-reduce leaves bucket-by-bucket in the plan's slot order.

    Must run inside shard_map with `dp_axes` un-mapped (i.e. per-shard
    code).  Returns the reduced leaves.  Buckets whose plan splits bytes
    across axes reduce in two stages (axis A then axis B) which on a
    torus is the standard 2-D ring reduction the plan load-balances."""
    order = plan.slot_order()
    out = {i: None for i in range(len(leaves))}
    token = None
    for slot_group in order:
        reduced_this_slot = []
        for b in slot_group:
            axes = [axis_names[a] for a in range(len(axis_names))
                    if plan.share[b, a].sum() > 1e-9 and axis_names[a] in dp_axes]
            if not axes:
                axes = list(dp_axes)
            for li in bucket_ids[b]:
                g = leaves[li]
                if token is not None:
                    g = _tie(g, token)
                for ax in axes:
                    g = jax.lax.psum(g, ax)
                out[li] = g
                reduced_this_slot.append(g)
        if reduced_this_slot:
            token = jax.lax.optimization_barrier(
                tuple(reduced_this_slot))[0]
    # leaves not covered by any bucket (shouldn't happen): reduce plainly
    for i, g in enumerate(leaves):
        if out[i] is None:
            for ax in dp_axes:
                g = jax.lax.psum(g, ax)
            out[i] = g
    return [out[i] for i in range(len(leaves))]


def _tie(x, token):
    """Make x depend on token without changing its value: add
    0 * (token's first element), which XLA cannot elide across the
    optimization barrier."""
    t0 = jnp.reshape(token, (-1,))[0].astype(x.dtype)
    return x + jnp.zeros_like(x) * t0


def make_scheduled_grad_sync(mesh: Mesh, plan: SlotPlan,
                             bucket_ids: list[list[int]],
                             dp_axes: Sequence[str] = ("data",)):
    """Return fn(grads)->grads that mean-reduces across dp_axes following
    the slot plan.  Grads must be replicated across dp_axes per-shard
    (pure DP layout) — used by examples/scheduled_training.py and tests."""
    axis_names = tuple(plan_axis_names(plan, mesh, dp_axes))
    n_dp = int(np.prod([mesh.shape[a] for a in dp_axes]))

    def sync(grads):
        leaves, tdef = jax.tree.flatten(grads)

        def inner(*ls):
            reduced = scheduled_psum(list(ls), bucket_ids, plan, axis_names,
                                     dp_axes)
            return tuple(r / n_dp for r in reduced)

        specs = tuple(P(*([None] * l.ndim)) for l in leaves)
        fn = shard_map(inner, mesh=mesh, in_specs=specs,
                       out_specs=specs)
        return jax.tree.unflatten(tdef, list(fn(*leaves)))

    return sync


def plan_axis_names(plan: SlotPlan, mesh: Mesh, dp_axes):
    names = []
    for a in range(plan.share.shape[1]):
        names.append(dp_axes[a] if a < len(dp_axes) else
                     list(mesh.shape.keys())[a % len(mesh.shape)])
    return names
