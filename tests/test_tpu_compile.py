"""The main path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler is installed, and it compiles for a
topology that is described, not attached. Nothing runs, so these tests
say nothing about results or speed; they catch what interpret mode
cannot (tiling, VMEM budgets, Mosaic lowering) before chip time is
spent. The topology is described inside a fixture, never at import:
only one process at a time may load the TPU library, and the driver
runs the suite with several workers. Keep every such test in this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from rabit_tpu.ops import pallas_kernels as PK

ROWS = 1 << 21   # bench.py's rows per worker


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def compiled_kernels(monkeypatch):
    """Compiled Mosaic, not interpret mode; and no persistent cache: a
    compile for a described chip is written to it but cannot be read
    back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.delenv("RABIT_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("RABIT_FLASH_BWD", raising=False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.mark.parametrize("precision", ["high", "fast"])
@pytest.mark.parametrize("nbins", [1024, 28 * 256])
def test_histogram_kernel_compiles(one_chip, nbins, precision):
    """bench.py's cell (1024 bins) and the HIGGS gpu_hist shape (28
    features x 256 bins), at 2,097,152 rows."""
    row = lambda dt: jax.ShapeDtypeStruct((ROWS,), dt,  # noqa: E731
                                          sharding=one_chip)
    compiled = PK._histogram_tpu_impl.lower(
        row(jnp.int32), row(jnp.float32), row(jnp.float32), nbins=nbins,
        precision=precision, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_histogram_kernel_keeps_its_name(topo):
    """distributed_histogram.lower (the jitted program under the public
    wrapper) at an unaligned row count: the build kernel's instruction
    carries the stable name a device trace shows, not a function's."""
    from rabit_tpu.models import histogram as H
    mesh = Mesh(topo.devices[:1], ("workers",))
    sh = NamedSharding(mesh, P("workers"))
    row = lambda dt: jax.ShapeDtypeStruct((1, 3 * PK._CHUNK + 5), dt,  # noqa: E731
                                          sharding=sh)
    text = H.distributed_histogram.lower(
        row(jnp.float32), row(jnp.float32), row(jnp.int32), nbins=28 * 256,
        mesh=mesh, method="pallas").compile().as_text()
    kernels = re.findall(r"%([\w.]+) = .* custom-call\(.*"
                         r'custom_call_target="tpu_custom_call"', text)
    assert [re.sub(r"\.\d+$", "", k) for k in kernels] == \
        [PK.HIST_KERNEL_NAME] == ["rabit_hist_build"]


def _tpu_kernels(text):
    names = re.findall(r"%([\w.]+) = .* custom-call\(.*"
                       r'custom_call_target="tpu_custom_call"', text)
    return [re.sub(r"\.\d+$", "", k) for k in names]


def _elements(hlo_type):
    """Element count of each array in an HLO result type."""
    return [int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
            for dims in re.findall(r"\w+\[([\d,]*)\]", hlo_type)]


@pytest.mark.parametrize("n, precision", [
    (1_312_500 * 28, "high"), (1_312_500 * 28, "fast"),
    (96 * 28, "high"), (96 * 28, "fast")],
    ids=["high", "fast", "node-high", "node-fast"])
def test_flat_histogram_at_higgs_shape_adds_no_copy(topo, n, precision):
    """distributed_histogram in the flat form at one HIGGS rank
    (1,312,500 rows x 28 features, 7,168 bins) and at one node of 96 rows,
    less than one kernel block: its one kernel is the flat build, which
    takes the program's three [1, n] parameters as they are, and no op
    copies, pads, converts or relayouts them, so the program's
    temporaries hold no copy of the inputs (882,339,840 B at "high" and
    588,214,272 B at "fast" when the program padded them and split hi/lo
    outside the kernel)."""
    from rabit_tpu.models import histogram as H
    mesh = Mesh(topo.devices[:1], ("workers",))
    sh = NamedSharding(mesh, P("workers"))
    row = lambda dt: jax.ShapeDtypeStruct((1, n), dt,  # noqa: E731
                                          sharding=sh)
    compiled = H.distributed_histogram.lower(
        row(jnp.float32), row(jnp.float32), row(jnp.int32), nbins=28 * 256,
        mesh=mesh, method="pallas", precision=precision).compile()
    text = compiled.as_text()
    assert _tpu_kernels(text) == [PK.HIST_KERNEL_NAME]
    params = dict(re.findall(r"%([\w.]+) = \S+ parameter\((\d)\)", text))
    call = re.search(r"= \S+ custom-call\(([^)]*)\).*tpu_custom_call", text)
    assert [params.get(a.strip().lstrip("%")) for a in
            call.group(1).split(",")] == ["2", "0", "1"]  # bins, grad, hess
    padded = -(-n // PK._CHUNK) * PK._CHUNK
    moved = [ln.strip()[:200] for ln in text.splitlines()
             if (m := re.search(r"= (.*?) (reduce|pad|concatenate|copy|"
                                r"convert|fusion)\(", ln))
             and any(e >= n and (e % n == 0 or e % padded == 0)
                     for e in _elements(m.group(1)))]
    assert moved == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize("precision", ["high", "fast"])
def test_flat_kernel_splits_hi_lo_inside(one_chip, precision):
    """At "high" the flat kernel splits each loaded block of grad and
    hess itself: its lowered Mosaic body (the custom call's payload)
    keeps both bf16 round-trips, truncf then extf, and subtracts each
    hi part from its input, which an excess-precision fold would have
    turned into exact zeros; at "fast" there is no split."""
    import base64
    import json
    from jax._src.lib.mlir import ir
    row = lambda dt: jax.ShapeDtypeStruct((1, 3 * PK._CHUNK + 5), dt,  # noqa: E731
                                          sharding=one_chip)
    text = PK._histogram_tpu_impl.lower(
        row(jnp.int32), row(jnp.float32), row(jnp.float32), nbins=28 * 256,
        precision=precision, interpret=False).as_text()
    config = re.search(r'backend_config = "(.*?)"', text).group(1)
    config = json.loads(re.sub(r"\\([0-9A-Fa-f]{2})",
                               lambda m: chr(int(m.group(1), 16)), config))
    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        body = str(ir.Module.parse(
            base64.b64decode(config["custom_call_config"]["body"])))
    vec = rf"vector<1x{PK._CHUNK}x"
    trunc = re.findall(r"%(\d+) = \S*arith\.truncf\S* ?\(%(\d+)\) : "
                       rf"\({vec}f32>\)", body)
    ext = dict(re.findall(r"%(\d+) = \S*arith\.extf\S* ?\(%(\d+)\)", body))
    sub = re.findall(r"arith\.subf\S* ?\(%(\d+), %(\d+)\)", body)
    split = {(x, e) for e, t in ext.items() for t2, x in trunc if t == t2}
    assert len(split) == (2 if precision == "high" else 0)
    assert split <= set(sub)


@pytest.mark.parametrize("precision", ["high", "fast"])
def test_dense_histogram_compiles_without_copying_bins(topo, precision):
    """distributed_histogram in the dense form at one epsilon rank
    (100,000 rows x 2,000 features, 512,000 bins): its only kernel is the
    dense build, and nothing pads, concatenates, copies or transposes the
    [n, F] bins matrix (the TPU lays it out feature-major, which is how
    the kernel reads it), so the program needs no temporary of its size;
    the flat form still names the flat kernel."""
    from rabit_tpu.models import histogram as H
    n, nfeat = 100_000, 2_000
    mesh = Mesh(topo.devices[:1], ("workers",))
    sh = NamedSharding(mesh, P("workers"))
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                  sharding=sh)
    compiled = H.distributed_histogram.lower(
        spec((1, n), jnp.float32), spec((1, n), jnp.float32),
        spec((1, n, nfeat), jnp.int32), nbins=nfeat * 256, mesh=mesh,
        method="pallas", precision=precision).compile()
    text = compiled.as_text()
    assert _tpu_kernels(text) == [PK.HIST_DENSE_KERNEL_NAME] == \
        ["rabit_hist_build_dense"]
    big = {f"[1,{n},{nfeat}]", f"[{n},{nfeat}]", f"[{nfeat},{n}]"}
    moved = [ln.strip()[:200] for ln in text.splitlines()
             if re.search(r" (pad|concatenate|copy|transpose)\(", ln)
             and any(s in ln.split("=")[1].split("(")[0] for s in big)]
    assert moved == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
    flat = lambda dt: spec((1, 3 * PK._CHUNK + 5), dt)  # noqa: E731
    text = H.distributed_histogram.lower(
        flat(jnp.float32), flat(jnp.float32), flat(jnp.int32),
        nbins=28 * 256, mesh=mesh, method="pallas",
        precision=precision).compile().as_text()
    assert _tpu_kernels(text) == [PK.HIST_KERNEL_NAME] == ["rabit_hist_build"]


@pytest.mark.parametrize("backward", [False, True],
                         ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("t", [512, 1024])
def test_flash_block_compiles(one_chip, t, backward):
    """flash_block at H=8, T=S=t, D=128, causal; with ``backward`` the
    fused Pallas backward as well. At T=S=2048 the fused backward runs
    out of VMEM (see ``_flash_compiler_params``)."""
    h, d = 8, 128
    blk = jax.ShapeDtypeStruct((h, t, d), jnp.bfloat16, sharding=one_chip)
    row = jax.ShapeDtypeStruct((h, t), jnp.float32, sharding=one_chip)
    acc = jax.ShapeDtypeStruct((h, t, d), jnp.float32, sharding=one_chip)

    def fwd(q, k, v, m, l, o):
        pos = jnp.arange(t)
        return PK.flash_block(q, k, v, m, l, o, pos[None, :] > pos[:, None],
                              1.0 / np.sqrt(d))

    fn = (jax.grad(lambda *a: sum(x.sum() for x in fwd(*a)),
                   argnums=(0, 1, 2)) if backward else fwd)
    compiled = jax.jit(fn).lower(blk, blk, blk, row, row, acc).compile()
    assert "tpu_custom_call" in compiled.as_text()
