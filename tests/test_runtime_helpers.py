"""Unit tests for the runtime helpers the sharded PDHG path leans on:
repro.runtime.collectives (shard_map, bucketize,
scheduled_psum via make_scheduled_grad_sync, plan_axis_names) and
repro.runtime.sharding (solver_mesh, Strategy spec derivation).

Everything here runs on the main process's single real CPU device —
1-device meshes make psum/pmean identities, so the plumbing (tracing
through shard_map, slot-ordered reduction, spec construction) is
exercised without multi-device subprocesses (tests/test_scale.py and
tests/test_distributed.py cover those).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import fabric
from repro.runtime import collectives as rc
from repro.runtime import sharding as rs


# ---------------------------------------------------------------- collectives
def test_shard_map_alias_is_callable_on_one_device_mesh():
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("shard",))
    fn = rc.shard_map(lambda x: jax.lax.psum(x, "shard"), mesh=mesh,
                      in_specs=P("shard"), out_specs=P("shard"),
                      check_vma=False)
    out = fn(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out), np.arange(4.0))


def test_bucketize_covers_all_leaves_exactly_once():
    leaves = [jnp.zeros((n,), jnp.float32) for n in (3, 5, 2, 7, 1)]
    buckets = rc.bucketize(leaves, bucket_bytes=4 * 6)   # ~6 floats/bucket
    flat = [i for b in buckets for i in b]
    assert sorted(flat) == list(range(len(leaves)))
    # backward order: the LAST leaf lands in the FIRST bucket
    assert flat[0] == len(leaves) - 1


def test_bucketize_one_leaf_per_bucket_when_budget_tiny():
    leaves = [jnp.zeros((4,), jnp.float32)] * 3
    assert rc.bucketize(leaves, bucket_bytes=1) == [[2], [1], [0]]


def test_bucketize_single_bucket_when_budget_huge():
    leaves = [jnp.zeros((4,), jnp.float32)] * 3
    assert rc.bucketize(leaves, bucket_bytes=1e9) == [[2, 1, 0]]


def test_scheduled_grad_sync_identity_on_one_device():
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    grads = {"w": jnp.arange(6.0).reshape(2, 3),
             "b": [jnp.ones((3,)) * 0.5, jnp.full((2, 2), -2.0)]}
    leaves, _ = jax.tree.flatten(grads)
    bucket_ids = rc.bucketize(leaves, bucket_bytes=16)
    spec = fabric.v5e_fabric()
    buckets = [fabric.Bucket(f"b{i}", 1e6, (0,), min(i, 3))
               for i in range(len(bucket_ids))]
    plan = fabric.plan_collectives(spec, buckets, n_slots=4)
    sync = rc.make_scheduled_grad_sync(mesh, plan, bucket_ids,
                                       dp_axes=("data",))
    out = sync(grads)
    # n_dp == 1: the slot-ordered psum-mean must be an exact identity
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(grads)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_plan_axis_names_prefers_dp_axes_then_mesh_axes():
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    spec = fabric.v5e_fabric()
    plan = fabric.plan_collectives(
        spec, [fabric.Bucket("b0", 1e6, (0,), 0)], n_slots=2)
    names = rc.plan_axis_names(plan, mesh, dp_axes=("data",))
    assert len(names) == plan.share.shape[1]
    assert names[0] == "data"
    assert all(n in ("data", "model") for n in names)


# ------------------------------------------------------------------- sharding
def test_solver_mesh_one_shard():
    mesh = rs.solver_mesh(1)
    assert mesh.axis_names == ("shard",)
    assert mesh.shape["shard"] == 1


def test_solver_mesh_custom_axis_name():
    assert rs.solver_mesh(1, axis="rows").axis_names == ("rows",)


def test_solver_mesh_rejects_nonpositive():
    with pytest.raises(ValueError, match=">= 1"):
        rs.solver_mesh(0)


def test_solver_mesh_too_many_devices_mentions_xla_flags():
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        rs.solver_mesh(99)


def test_strategy_fsdp_spec_shards_largest_divisible_dim():
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    st = rs.Strategy(mesh=mesh, kind="fsdp", multi_pod=False)
    # 1-device axes divide everything: largest dim gets the axis tuple
    spec = st._fsdp_spec((4, 8))
    assert spec[1] is not None and spec[0] is None
    assert st._fsdp_spec(()) == P()


def test_strategy_batch_axes_by_kind():
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    assert rs.Strategy(mesh, "fsdp", multi_pod=False).batch_axes == \
        ("data", "model")
    assert rs.Strategy(mesh, "2d", multi_pod=False).batch_axes == ("data",)


def test_strategy_logical_to_spec_dedupes_mesh_axes():
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    st = rs.Strategy(mesh=mesh, kind="2d", multi_pod=False)
    spec = st.logical_to_spec(("batch", "seq", "heads"), (2, 8, 4))
    flat = [a for ax in spec
            for a in (ax if isinstance(ax, tuple) else (ax,)) if a]
    assert len(flat) == len(set(flat)), f"duplicate mesh axis in {spec}"
