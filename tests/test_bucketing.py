"""Gradient bucketing (DDP-style): the whole gradient tree syncs as ONE
contiguous buffer per dtype instead of one collective per parameter
leaf. The tests pin the two claims that justify the feature:

1. dispatch count — the compiled train step must contain measurably
   fewer collective dispatches under ``grad_sync="bucket"`` than under
   per-leaf ``grad_sync="ring"`` (asserted on the jaxpr, where each
   ``ppermute`` equation is one wire dispatch);
2. numerics — the loss trajectory must match the checked ``psum`` path
   (same reduction, different packing).

Plus the host-level ``device_allreduce_tree`` correctness (mixed-dtype
tree, per-dtype-bucket dispatch).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rabit_tpu.ops.reducers import SUM
from rabit_tpu.parallel import make_mesh, device_allreduce_tree
from rabit_tpu.parallel.collectives import shard_over
from rabit_tpu.models import mlp

NDEV = len(jax.devices())
pytestmark = pytest.mark.skipif(NDEV < 8, reason="needs 8 virtual devices")

_COLLECTIVES = ("ppermute", "psum", "pmax", "pmin", "all_gather",
                "all_to_all", "reduce_scatter")


def _count_eqns(jaxpr, names) -> int:
    """Primitive occurrences in a jaxpr, recursing into sub-jaxprs
    (pjit / shard_map / custom_vjp / scan all nest theirs in params)."""
    from jax.extend.core import Jaxpr, ClosedJaxpr
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            n += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if isinstance(sub, ClosedJaxpr):
                    n += _count_eqns(sub.jaxpr, names)
                elif isinstance(sub, Jaxpr):
                    n += _count_eqns(sub, names)
    return n


def _dispatch_count(grad_sync, names=("ppermute",)) -> int:
    mesh = make_mesh(8, ("dp", "tp"), (4, 2))
    params, x, y = mlp.make_sharded_inputs(
        mesh, batch=16, in_dim=12, hidden=8, out_dim=4, seed=7)
    step = mlp.make_train_step(mesh, lr=0.5, grad_sync=grad_sync)
    return _count_eqns(jax.make_jaxpr(step)(params, x, y).jaxpr, names)


def test_bucket_reduces_dispatch_count():
    """The headline claim: 4 parameter leaves, all float32 -> ONE bucket
    -> one ring dispatch chain where per-leaf sync issues four."""
    ring = _dispatch_count("ring")
    bucket = _dispatch_count("bucket")
    assert bucket < ring, (bucket, ring)
    # exactly one ring over dp=4 remains: (p-1) reduce-scatter +
    # (p-1) all-gather ppermutes = 6; per-leaf pays that 4x
    assert bucket == 6, bucket
    assert ring == 24, ring


def test_bucket_loss_trajectory_matches_per_leaf():
    """Bucketing repacks gradients; it must not change what is computed.
    Baseline is the per-leaf ring path (the checked psum path needs
    replication inference this jax version's shard_map can't do — a
    known environment gap, see test_models' psum-mode xfails)."""
    mesh = make_mesh(8, ("dp", "tp"), (4, 2))

    def run(grad_sync, steps=5):
        params, x, y = mlp.make_sharded_inputs(
            mesh, batch=32, in_dim=16, hidden=16, out_dim=4, seed=0)
        step = mlp.make_train_step(mesh, lr=0.2, grad_sync=grad_sync)
        losses = []
        for _ in range(steps):
            params, loss = step(params, x, y)
            losses.append(float(loss))
        return losses

    ref = run("ring")
    got = run("bucket")
    assert got[-1] < got[0], got  # still actually training
    # same reduction, different packing/order: f32 round-off only
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=1e-3)


def test_bucket_first_step_matches_dense_reference():
    """One bucketed SPMD step against the single-device step — the
    strongest oracle available (no collective at all on that side)."""
    mesh = make_mesh(8, ("dp", "tp"), (4, 2))
    params, x, y = mlp.make_sharded_inputs(
        mesh, batch=16, in_dim=12, hidden=8, out_dim=4, seed=7)
    step = mlp.make_train_step(mesh, lr=0.5, grad_sync="bucket")
    new_params, loss = step(params, x, y)

    host = {k: jnp.asarray(np.asarray(v)) for k, v in params.items()}
    ref_params, ref_loss = mlp.reference_train_step(
        host, jnp.asarray(np.asarray(x)), jnp.asarray(np.asarray(y)),
        lr=0.5)
    assert np.isclose(float(loss), float(ref_loss), rtol=2e-2, atol=1e-3)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(new_params[k]), np.asarray(ref_params[k]),
            rtol=5e-2, atol=5e-3)


def test_device_allreduce_tree_mixed_dtypes():
    """Host-level bucketed allreduce: mixed-dtype tree -> one bucket per
    dtype, every leaf reduced exactly, structure preserved."""
    mesh = make_mesh(8)
    p = 8
    rng = np.random.default_rng(21)
    host = {
        "w": rng.standard_normal((p, 33, 5)).astype(np.float32),
        "b": rng.standard_normal((p, 17)).astype(np.float32),
        "steps": rng.integers(0, 1000, (p, 9)).astype(np.int32),
        "flags": rng.integers(0, 100, (p, 3)).astype(np.int32),
    }
    tree = {k: shard_over(mesh, v) for k, v in host.items()}
    out = device_allreduce_tree(tree, mesh, SUM)
    assert set(out) == set(host)
    for k, v in host.items():
        got = np.asarray(out[k])
        assert got.shape == v.shape[1:]
        assert got.dtype == v.dtype
        if v.dtype == np.float32:
            np.testing.assert_allclose(got, v.sum(0), rtol=1e-5, atol=1e-5)
        else:
            np.testing.assert_array_equal(got, v.sum(0))


def test_device_allreduce_tree_empty_and_identity():
    mesh = make_mesh(8)
    assert device_allreduce_tree({}, mesh, SUM) == {}
    xs = np.ones((8, 4), np.float32)
    out = device_allreduce_tree([shard_over(mesh, xs)], mesh, SUM)
    np.testing.assert_allclose(np.asarray(out[0]), np.full(4, 8.0))


def test_device_allreduce_tree_explicit_method():
    mesh = make_mesh(8)
    xs = np.arange(8 * 100, dtype=np.int32).reshape(8, 100)
    for method in ("tree", "ring", "bidir", "swing"):
        out = device_allreduce_tree({"g": shard_over(mesh, xs)}, mesh, SUM,
                                    method=method)
        np.testing.assert_array_equal(np.asarray(out["g"]), xs.sum(0))
