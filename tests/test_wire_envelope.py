"""Quantized-wire error envelope at scale (VERDICT r4 #6).

tests/test_collectives.py pins the envelope at world 8; these runs pin
it on LARGE virtual meshes — p=64 and p=128 — where quantization error
has accumulated over p-1 ring hops. The measured growth is ~sqrt(p)
(bf16: 0.014 @ p=8 -> ~0.037 @ p=64; int8: ~0.054 @ p=128), and the
asserted bound is the same ``2e-2 * sqrt(p)`` the multichip dryrun
allows (__graft_entry__.py) and doc/guide.md documents.

Each case needs its own device count, which XLA fixes at backend init —
so the measurement runs in a subprocess with its own XLA_FLAGS (the
conftest pins this process to 8 virtual devices).
"""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = """\
import os, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {root!r})
from rabit_tpu.parallel.collectives import device_allreduce, SUM
from tests.test_collectives import make_mesh, shard_over

p = {p}
wire = {wire!r}
mesh = make_mesh(p)
rng = np.random.default_rng(7)
n = p * 256  # per-rank chunk = one int8 block
xs = rng.standard_normal((p, n)).astype(np.float32)
want = xs.sum(axis=0)
out = device_allreduce(shard_over(mesh, xs), mesh, SUM,
                       method="ring", wire=wire)
got = np.asarray(out)
rel = np.abs(got - want).max() / np.abs(want).max()
assert rel < 2e-2 * np.sqrt(p), (wire, p, rel)
# quantization must actually be engaged: an exact result would mean
# the wire path silently fell back to f32
assert rel > 1e-4, (wire, p, rel)
# every rank bit-identical — the replay/recovery contract holds at
# scale, not only at world 8
shards = [np.asarray(out.addressable_data(i)) for i in range(p)]
for i in range(1, p):
    assert np.array_equal(shards[0], shards[i]), (wire, i)
print(f"ENVELOPE-OK {{wire}} p={{p}} rel={{rel:.4f}}")
"""


@pytest.mark.parametrize("p", [64, 128])
@pytest.mark.parametrize("wire", ["bf16", "int8"])
def test_wire_envelope_at_scale(p, wire, tmp_path):
    prog = tmp_path / "probe.py"
    prog.write_text(PROBE.format(root=ROOT, p=p, wire=wire))
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={p}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ROOT  # a pure-CPU measurement of this checkout
    out = subprocess.run([sys.executable, str(prog)], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, (out.stdout[-500:], out.stderr[-1500:])
    assert f"ENVELOPE-OK {wire} p={p}" in out.stdout, out.stdout
