"""Model workloads on the virtual mesh: histogram (north-star) and the
flagship SPMD MLP training step."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rabit_tpu.parallel import make_mesh
from rabit_tpu.parallel.collectives import shard_over
from rabit_tpu.models import histogram as H
from rabit_tpu.models import mlp

NDEV = len(jax.devices())
pytestmark = pytest.mark.skipif(NDEV < 8, reason="needs 8 virtual devices")


@pytest.mark.parametrize("method", ["pallas", "scatter"])
def test_distributed_histogram(monkeypatch, method):
    monkeypatch.setenv("RABIT_PALLAS_INTERPRET", "1")
    p, n, nbins = 8, 4096, 64
    grad, hess, bins = H.make_inputs(n, nbins, p=p, seed=3)
    mesh = make_mesh(p)
    out = np.asarray(H.distributed_histogram(
        shard_over(mesh, grad), shard_over(mesh, hess),
        shard_over(mesh, bins), nbins, mesh, "workers", method))
    want = np.zeros((nbins, 2), np.float64)
    for i in range(p):
        want += H.host_histogram(grad[i], hess[i], bins[i], nbins)
    np.testing.assert_allclose(out, want, rtol=1e-3, atol=1e-3)


def test_local_histogram_padding(monkeypatch):
    # n not divisible by the kernel's step: padding entries must not leak
    monkeypatch.setenv("RABIT_PALLAS_INTERPRET", "1")
    n, nbins = 1000, 16
    grad, hess, bins = (a[0] for a in H.make_inputs(n, nbins, p=1, seed=1))
    out = np.asarray(H.local_histogram(
        jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(bins), nbins,
        method="pallas"))
    want = H.host_histogram(grad, hess, bins, nbins)
    np.testing.assert_allclose(out, want, rtol=1e-3, atol=1e-3)


def test_histogram_tpu_pads_any_n(monkeypatch):
    """The flat kernel's entry pads an unaligned n itself: bit for bit
    the call on entries padded by hand with bin id nbins and zero
    weights."""
    monkeypatch.setenv("RABIT_PALLAS_INTERPRET", "1")
    from rabit_tpu.ops.pallas_kernels import histogram_tpu, _CHUNK
    n, nbins = 1000, 64
    grad, hess, bins = (a[0] for a in H.make_inputs(n, nbins, p=1, seed=2))
    pad = _CHUNK - n
    padded = histogram_tpu(
        jnp.asarray(np.concatenate([bins, np.full(pad, nbins, bins.dtype)])),
        jnp.asarray(np.concatenate([grad, np.zeros(pad, grad.dtype)])),
        jnp.asarray(np.concatenate([hess, np.zeros(pad, hess.dtype)])),
        nbins)
    got = histogram_tpu(jnp.asarray(bins), jnp.asarray(grad),
                        jnp.asarray(hess), nbins)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(padded))


@pytest.mark.parametrize("precision", ["high", "fast"])
@pytest.mark.parametrize("n", [1, 127, 2688, 16383, 16385, 3 * 16384 + 5])
def test_histogram_tpu_masks_its_last_block(monkeypatch, n, precision):
    """The flat kernel reads unpadded inputs as handed and masks the
    entries of its last block that lie past n: with interpret mode
    filling them with bin 0 and NaN weights, it matches the host oracle
    at sizes under one block, around one block and past three, and the
    [n] and [1, n] forms give the same bits."""
    from jax._src.pallas import primitives
    monkeypatch.setenv("RABIT_PALLAS_INTERPRET", "1")
    fill = primitives.uninitialized_value
    monkeypatch.setattr(   # a bin in range, where INT_MIN is in none
        primitives, "uninitialized_value",
        lambda shape, dtype: (jnp.zeros(shape, dtype)
                              if jnp.issubdtype(dtype, jnp.integer)
                              else fill(shape, dtype)))
    from rabit_tpu.ops.pallas_kernels import histogram_tpu
    nbins = 28 * 256
    grad, hess, bins = (a[0] for a in H.make_inputs(n, nbins, p=1, seed=6))
    flat = np.asarray(histogram_tpu(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), nbins,
        precision=precision))
    row = np.asarray(histogram_tpu(
        *(jnp.asarray(a[None]) for a in (bins, grad, hess)), nbins,
        precision=precision))
    np.testing.assert_array_equal(flat, row)
    want = H.host_histogram(grad, hess, bins, nbins)
    atol = 0.1 if precision == "fast" else 1e-4
    np.testing.assert_allclose(flat, want, rtol=0, atol=atol)


@pytest.mark.parametrize("precision", ["high", "fast"])
def test_components_split(precision):
    """The weights both kernels take. At "high", under jit as the
    kernels' callers run it: hi parts bf16-representable, hi + lo the
    f32 input exactly, and lo non-zero wherever bf16 cannot hold the
    input. An XLA allowed excess precision may fold the bf16 round-trip
    and zero every lo, which the CPU backend does not do, so the program
    must also keep its optimization barrier. At "fast", the inputs
    themselves."""
    from rabit_tpu.ops.pallas_kernels import _components
    rng = np.random.default_rng(4)
    grad = rng.standard_normal(4096).astype(np.float32)
    hess = rng.random(4096).astype(np.float32)
    split = jax.jit(_components, static_argnames="precision")
    args = jnp.asarray(grad), jnp.asarray(hess)
    assert ("optimization_barrier" in split.lower(
        *args, precision=precision).as_text()) == (precision == "high")
    comps = [np.asarray(c) for c in split(*args, precision=precision)]
    if precision == "fast":
        assert len(comps) == 2
        np.testing.assert_array_equal(comps[0], grad)
        np.testing.assert_array_equal(comps[1], hess)
        return
    assert len(comps) == 4
    for x, hi, lo in ((grad, comps[0], comps[2]), (hess, comps[1], comps[3])):
        np.testing.assert_array_equal(
            hi.astype(jnp.bfloat16).astype(np.float32), hi)
        np.testing.assert_array_equal(hi + lo, x)
        inexact = x.astype(jnp.bfloat16).astype(np.float32) != x
        assert inexact.sum() > 4000
        assert np.all(lo[inexact] != 0)


@pytest.mark.parametrize("precision", ["fast", "high"])
@pytest.mark.parametrize("nbins", [64, 16640, 28 * 256])
def test_local_histogram_pallas_interpret(monkeypatch, precision, nbins):
    """The pallas kernel (interpret mode on CPU) matches the host oracle
    at its documented precision, including padding rows. nbins=64 is
    fewer bins than one lo tile; 28*256 is HIGGS's one a-tile of 56 hi
    groups; 16640 (130 hi groups) ends in a partial a-tile. Entries with
    bin id nbins, planted among the real ones with large weights and
    padding the tail to the chunk, contribute nothing."""
    monkeypatch.setenv("RABIT_PALLAS_INTERPRET", "1")
    from rabit_tpu.ops.pallas_kernels import histogram_tpu, _CHUNK
    n = 10_000
    grad, hess, bins = (a[0] for a in H.make_inputs(n, nbins, p=1, seed=5))
    planted = np.random.default_rng(0).choice(n, 50, replace=False)
    b, g, h = bins.copy(), grad.copy(), hess.copy()
    b[planted], g[planted], h[planted] = nbins, 1e3, 1e3
    pad = (-n) % _CHUNK
    assert pad
    b = np.concatenate([b, np.full(pad, nbins, bins.dtype)])
    g = np.concatenate([g, np.zeros(pad, grad.dtype)])
    h = np.concatenate([h, np.zeros(pad, hess.dtype)])
    out = np.asarray(histogram_tpu(
        jnp.asarray(b), jnp.asarray(g), jnp.asarray(h), nbins,
        precision=precision))
    keep = np.ones(n, bool)
    keep[planted] = False
    want = H.host_histogram(grad[keep], hess[keep], bins[keep], nbins)
    atol = 0.5 if precision == "fast" else 1e-3
    np.testing.assert_allclose(out, want, rtol=2e-2, atol=atol)


@pytest.mark.parametrize("nbins, r, tiles", [
    (64, 2, (8, 1)), (1024, 4, (8, 1)), (28 * 256, 4, (56, 1)),
    (28 * 256, 2, (56, 1)), (16640, 4, (72, 2)), (16640, 2, (136, 1)),
    (2000 * 256, 4, (128, 32))])
def test_histogram_kernel_a_tiles(nbins, r, tiles):
    """The flat kernel's a-tiles cover every hi group, in multiples of 8,
    with a fused operand of at most 512 rows."""
    from rabit_tpu.ops.pallas_kernels import _atiles
    atile, nat = _atiles(nbins, r)
    assert (atile, nat) == tiles
    assert atile * nat * 128 >= nbins and r * atile <= 512


def test_histogram_bad_precision_rejected(monkeypatch):
    monkeypatch.setenv("RABIT_PALLAS_INTERPRET", "1")
    from rabit_tpu.ops.pallas_kernels import histogram_tpu, _CHUNK
    z = jnp.zeros(_CHUNK, jnp.int32)
    with pytest.raises(ValueError, match="precision"):
        histogram_tpu(z, z.astype(jnp.float32), z.astype(jnp.float32),
                      16, precision="exact")


def test_mlp_spmd_matches_single_device():
    """The hand-sharded dp x tp training step must match the plain
    single-device step numerically (same init, same batch)."""
    mesh = make_mesh(8, ("dp", "tp"), (4, 2))
    params, x, y = mlp.make_sharded_inputs(
        mesh, batch=16, in_dim=12, hidden=8, out_dim=4, seed=7)
    step = mlp.make_train_step(mesh, lr=0.5)
    new_params, loss = step(params, x, y)

    host_params = {k: np.asarray(v) for k, v in params.items()}
    ref_params, ref_loss = mlp.reference_train_step(
        {k: jnp.asarray(v) for k, v in host_params.items()},
        jnp.asarray(np.asarray(x)), jnp.asarray(np.asarray(y)), lr=0.5)

    assert np.isclose(float(loss), float(ref_loss), rtol=2e-2, atol=1e-3)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(new_params[k]), np.asarray(ref_params[k]),
            rtol=5e-2, atol=5e-3)


def test_mlp_training_reduces_loss():
    mesh = make_mesh(8, ("dp", "tp"), (4, 2))
    params, x, y = mlp.make_sharded_inputs(
        mesh, batch=32, in_dim=16, hidden=16, out_dim=4, seed=0)
    step = mlp.make_train_step(mesh, lr=0.2)
    losses = []
    for _ in range(5):
        params, loss = step(params, x, y)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    assert all(np.isfinite(l) for l in losses)


def test_py_long_context_example():
    """The long-context example runs standalone (pure JAX, no native
    core, no tracker); lives here rather than test_examples.py so a
    failed native build doesn't skip it."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if "host_platform_device_count" not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=8")
    out = subprocess.run(
        [sys.executable, os.path.join(root, "examples", "py",
                                      "long_context.py")],
        capture_output=True, timeout=300, env=env, text=True)
    assert out.returncode == 0, out.stdout + out.stderr


def test_graft_entry_contract():
    """The driver contract: entry() returns a jittable fn + args, and
    dryrun_multichip(8) compiles+runs the full sharded training step."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "__graft_entry__",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "__graft_entry__.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    fn, args = mod.entry()
    out = fn(*args)
    assert out.shape == (4, 256, 256)  # [batch, seq, vocab] logits
    mod.dryrun_multichip(8)
