"""In-collective block-wise quantization (ISSUE 16, EQuARX-style).

Pins the four contracts the block-quantized dataplane ships under:

1. **Error envelopes** per (wire spec, schedule, dtype): every
   phase-split / block-size combination stays inside the documented
   ``2e-2 * sqrt(p)`` relative bound at world 8, while actually
   engaging (an exact result would mean the codec silently fell back
   to f32) — and every rank ends bit-identical (the replay contract).
2. **Bit-exactness when off**: ``wire=None`` and the ``"none"`` /
   ``"off"`` spellings produce byte-identical results, and the
   bucketed-MLP train step traces a byte-identical jaxpr with every
   new knob unset vs explicitly defaulted — the quantization plane
   adds ZERO equations when disabled.
3. **Adaptive election** (dispatch): a measured-slow fabric elects the
   requested wire with ``provenance="adaptive"`` and bumps the
   ``dispatch.wire_adapted`` / ``wire.quantized`` counters; a fast
   fabric declines; no telemetry falls through to the mincount gate.
   ``note_wire``/``last_wire`` expose the outcome the dataplane span
   stamps as ``wire_applied``.
4. **Spec grammar + v3 table validation**: canonical specs fold the
   env block exactly once, ``wire_itemsize`` prices phase splits, and
   the dispatch loader accepts v3 spec wire columns while rejecting
   junk.
"""

import json
import os

import numpy as np
import pytest

import jax

from rabit_tpu.ops.reducers import SUM, MAX
from rabit_tpu import telemetry
from rabit_tpu.parallel import dispatch, make_mesh, wire
from rabit_tpu.parallel.collectives import (
    device_allreduce, device_allgather, device_reduce_scatter,
    device_hier_allreduce, _normalize_wire, shard_over)

NDEV = len(jax.devices())
pytestmark = pytest.mark.skipif(NDEV < 8, reason="needs 8 virtual devices")

P = 8
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WIRE_KNOBS = ("RABIT_WIRE_BLOCK", "RABIT_WIRE_RS", "RABIT_WIRE_AG",
              "RABIT_WIRE_ADAPTIVE", "RABIT_DATAPLANE_WIRE",
              "RABIT_DATAPLANE_WIRE_MINCOUNT")


@pytest.fixture
def clean_knobs(monkeypatch):
    for k in WIRE_KNOBS:
        monkeypatch.delenv(k, raising=False)
    return monkeypatch


def _relerr(wire_spec, method="ring", dtype=np.float32, n=None,
            groups=None):
    mesh = make_mesh(P)
    rng = np.random.default_rng(13)
    n = n or P * 4096  # per-rank ring chunk tiles every tested block
    xs = rng.standard_normal((P, n)).astype(dtype)
    want = xs.astype(np.float64).sum(axis=0)
    if method == "hier":
        out = device_hier_allreduce(shard_over(mesh, xs), mesh, SUM,
                                    groups=groups, wire=wire_spec)
    else:
        out = device_allreduce(shard_over(mesh, xs), mesh, SUM,
                               method=method, wire=wire_spec)
    got = np.asarray(out).astype(np.float64)
    rel = np.abs(got - want).max() / np.abs(want).max()
    shards = [np.asarray(out.addressable_data(i)) for i in range(P)]
    for i in range(1, P):
        assert np.array_equal(shards[0], shards[i]), (wire_spec, i)
    return rel


# ---------------------------------------------------------------- envelopes
BOUND = 2e-2 * np.sqrt(P)  # the documented at-scale envelope

SPECS = ["bf16", "int8", "int8:bf16", "bf16:int8", "none:int8",
         "int8:none", "int8@256", "int8@4096", "int8:bf16@512"]


@pytest.mark.parametrize("method", ["ring", "bidir", "swing"])
@pytest.mark.parametrize("spec", SPECS)
def test_envelope_per_spec_and_method(method, spec):
    rel = _relerr(spec, method=method)
    assert rel < BOUND, (method, spec, rel)
    # the codec must actually engage — exact means silent f32 fallback
    assert rel > 1e-6, (method, spec, rel)


def test_envelope_hier_inter_phase():
    groups = ((0, 1, 2, 3), (4, 5, 6, 7))
    for spec in ("int8:bf16", "bf16", "int8@512"):
        rel = _relerr(spec, method="hier", groups=groups)
        assert 1e-7 < rel < BOUND, (spec, rel)


def test_envelope_bf16_dtype_payload():
    # a bf16 payload through the int8 codec: accumulate-in-f32 keeps
    # the ring sum at least as accurate as the input precision
    rel = _relerr("int8:bf16", dtype=jax.numpy.bfloat16)
    assert rel < 0.1, rel


def test_envelope_first_class_rs_ag():
    mesh = make_mesh(P)
    rng = np.random.default_rng(5)
    n = P * 2048
    xs = rng.standard_normal((P, n)).astype(np.float32)
    want = xs.sum(axis=0)
    rs = np.asarray(device_reduce_scatter(
        shard_over(mesh, xs), mesh, SUM, wire="int8@256"))
    rel = np.abs(rs.reshape(-1) - want).max() / np.abs(want).max()
    assert 1e-7 < rel < BOUND, rel
    row = rng.standard_normal((P, 512)).astype(np.float32)
    ag = np.asarray(device_allgather(shard_over(mesh, row), mesh,
                                     wire="bf16"))
    rel = np.abs(ag.reshape(-1) - row.reshape(-1)).max() / np.abs(row).max()
    assert 1e-7 < rel < 8e-3, rel


def test_block_size_monotonicity():
    # smaller scaling blocks track local magnitude better: error must
    # not degrade when the block shrinks 16x on the same payload
    rel_small = _relerr("int8@256")
    rel_big = _relerr("int8@4096")
    assert rel_small < rel_big * 1.5, (rel_small, rel_big)


# ------------------------------------------------------------- off == exact
def test_off_spellings_bitwise_identical():
    mesh = make_mesh(P)
    rng = np.random.default_rng(3)
    xs = rng.standard_normal((P, 4096)).astype(np.float32)
    outs = [np.asarray(device_allreduce(shard_over(mesh, xs), mesh, SUM,
                                        method="ring", wire=w))
            for w in (None, "none", "off")]
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[0], outs[2])


def test_non_sum_and_integer_payloads_ignore_wire():
    assert _normalize_wire("int8:bf16", MAX, np.dtype(np.float32)) is None
    assert _normalize_wire("int8", SUM, np.dtype(np.int32)) is None
    # non-tiling chunks degrade the int8 phase to bf16, never crash
    assert _normalize_wire("int8", SUM, np.dtype(np.float32),
                           chunk_len=100) == "bf16"


def test_bucketed_mlp_jaxpr_byte_identical_with_knobs_unset(clean_knobs):
    import re

    from rabit_tpu.models import mlp

    def trace():
        mesh = make_mesh(8, ("dp", "tp"), (4, 2))
        params, x, y = mlp.make_sharded_inputs(
            mesh, batch=16, in_dim=12, hidden=8, out_dim=4, seed=7)
        step = mlp.make_train_step(mesh, lr=0.5, grad_sync="bucket")
        s = str(jax.make_jaxpr(step)(params, x, y))
        # function reprs embed per-trace object addresses; the program
        # structure is what must be byte-identical
        return re.sub(r"0x[0-9a-f]+", "0x0", s)

    unset = trace()
    # explicit defaults must be indistinguishable from absent knobs —
    # the whole quantization plane contributes zero equations when off
    clean_knobs.setenv("RABIT_WIRE_BLOCK", "1024")
    clean_knobs.setenv("RABIT_WIRE_ADAPTIVE", "0")
    defaulted = trace()
    assert unset == defaulted
    assert "ppermute" in unset  # the ring itself is still there


# --------------------------------------------------------------- adaptive
def _seed_bandwidth(bw_gbps: float, n: int = 1 << 20, itemsize: int = 4,
                    rounds: int = 8) -> None:
    telemetry.reset(enabled=True)
    for _ in range(rounds):
        telemetry.record_span(
            "allreduce", (n * itemsize) / (bw_gbps * 1e9),
            nbytes=n * itemsize, method="ring")


def test_adaptive_elects_on_slow_fabric(clean_knobs):
    clean_knobs.setenv("RABIT_WIRE_ADAPTIVE", "1")
    clean_knobs.setenv("RABIT_WIRE_RS", "int8")
    clean_knobs.setenv("RABIT_WIRE_AG", "bf16")
    _seed_bandwidth(0.05)
    try:
        _, w = dispatch.resolve(1 << 20, np.dtype(np.float32), SUM,
                                P, method="ring", wire="auto")
        assert w == "int8:bf16", w
        assert dispatch.last_wire() == "int8:bf16"
        assert dispatch.last_wire_provenance() == "adaptive"
        assert telemetry.counter_rows("dispatch.wire_adapted")
        qrows = telemetry.counter_rows("wire.quantized")
        assert qrows and qrows[0]["bytes"] >= (1 << 20) * 4
    finally:
        telemetry.reset(enabled=False)


def test_adaptive_declines_on_fast_fabric(clean_knobs):
    clean_knobs.setenv("RABIT_WIRE_ADAPTIVE", "1")
    clean_knobs.setenv("RABIT_DATAPLANE_WIRE", "int8")
    clean_knobs.setenv("RABIT_DATAPLANE_WIRE_MINCOUNT", "1")
    _seed_bandwidth(1000.0)
    try:
        _, w = dispatch.resolve(1 << 20, np.dtype(np.float32), SUM,
                                P, method="ring", wire="auto")
        assert w is None, w
        assert dispatch.last_wire() is None
        assert dispatch.last_wire_provenance() == "adaptive"
    finally:
        telemetry.reset(enabled=False)


def test_adaptive_no_data_falls_through_to_gate(clean_knobs):
    clean_knobs.setenv("RABIT_WIRE_ADAPTIVE", "1")
    clean_knobs.setenv("RABIT_DATAPLANE_WIRE", "int8")
    clean_knobs.setenv("RABIT_DATAPLANE_WIRE_MINCOUNT", "1024")
    telemetry.reset(enabled=True)
    try:
        # no telemetry rows: the explicit mincount gate decides
        _, w = dispatch.resolve(1 << 20, np.dtype(np.float32), SUM,
                                P, method="ring", wire="auto")
        assert w == "int8", w
        _, w = dispatch.resolve(512, np.dtype(np.float32), SUM,
                                P, method="ring", wire="auto")
        assert w is None, w
    finally:
        telemetry.reset(enabled=False)


# ----------------------------------------------------- grammar + v3 tables
def test_canonical_wire_folds_env_block_once(clean_knobs):
    clean_knobs.setenv("RABIT_WIRE_BLOCK", "512")
    assert wire.canonical_wire("int8") == "int8@512"
    # a spec pinning its own block wins over the env
    assert wire.canonical_wire("int8@2048") == "int8@2048"
    clean_knobs.delenv("RABIT_WIRE_BLOCK")
    assert wire.canonical_wire("int8") == "int8"
    assert wire.canonical_wire("off") is None
    assert wire.canonical_wire(None) is None


def test_wire_itemsize_prices_phase_split():
    assert wire.wire_itemsize(None, 4) == 4.0
    assert wire.wire_itemsize("bf16", 4) == 2.0
    assert wire.wire_itemsize("int8@1024", 4) == 1.0 + 4.0 / 1024
    mixed = wire.wire_itemsize("int8:bf16@512", 4)
    assert mixed == ((1.0 + 4.0 / 512) + 2.0) / 2
    assert wire.wire_itemsize("none:int8", 4) == (4.0 + 1.0
                                                  + 4.0 / 1024) / 2


def test_dispatch_accepts_v3_spec_columns(tmp_path):
    doc = {"schema": "rabit_tpu.collective_sweep/v3", "backend": "cpu",
           "table": {"float_sum": [
               {"max_n": 1000, "method": "tree", "wire": None},
               {"max_n": None, "method": "ring",
                "wire": "int8:bf16@512"}],
               "other": [{"max_n": None, "method": "tree",
                          "wire": None}]}}
    good = tmp_path / "sweep_good.json"
    good.write_text(json.dumps(doc))
    dispatch.clear_cache()
    assert dispatch.load_table(str(good)) is not None
    doc["table"]["float_sum"][1]["wire"] = "fp4:garbage"
    bad = tmp_path / "sweep_bad.json"
    bad.write_text(json.dumps(doc))
    assert dispatch.load_table(str(bad)) is None
    dispatch.clear_cache()


def test_committed_artifact_is_v3_and_quantized_beats_ring():
    arts = sorted(a for a in os.listdir(
        os.path.join(ROOT, "benchmarks", "artifacts"))
        if a.startswith("COLLECTIVE_SWEEP_"))
    path = os.path.join(ROOT, "benchmarks", "artifacts", arts[-1])
    with open(path) as f:
        doc = json.load(f)
    assert doc["schema"] == "rabit_tpu.collective_sweep/v3"
    assert dispatch.load_table(path) is not None
    # the acceptance measurement: some quantized mode beats the
    # unquantized ring below 4M floats in the committed sweep
    by_n = {}
    for r in doc["rows"]:
        if r["section"] == "float_sum" and r["n"] < (4 << 20):
            by_n.setdefault(r["n"], []).append(r)
    beats = False
    for rs in by_n.values():
        ring = [r for r in rs if r["method"] == "ring"
                and r["wire"] is None]
        quant = [r for r in rs if r["wire"]]
        if ring and quant and min(q["s_per_op"] for q in quant) \
                < ring[0]["s_per_op"]:
            beats = True
    assert beats, "no quantized mode beats unquantized ring below 4M"
