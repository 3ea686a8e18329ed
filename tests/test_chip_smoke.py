"""Without a TPU nothing prints a device result: ``chip_smoke.py`` and
``bench.py`` (without ``RABIT_BENCH_SMOKE``) exit non-zero and print no
result line, and the compile cache goes where it is told."""

import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd=ROOT, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("RABIT_BENCH_SMOKE", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra)
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=300, env=env, cwd=cwd)


def _assert_no_result(out):
    assert out.returncode != 0, out.stdout[-2000:]
    lines = out.stdout.strip().splitlines()
    assert not lines or '"ok": true' not in lines[-1], lines[-1]
    assert not any(ln.lstrip().startswith("{") for ln in lines), lines


def test_chip_smoke_refuses_cpu():
    out = _run([os.path.join(ROOT, "chip_smoke.py")])
    _assert_no_result(out)
    assert "no TPU" in out.stderr


def test_chip_smoke_fails_without_the_repo(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    out = _run([str(tmp_path / "chip_smoke.py")], cwd=str(tmp_path),
               PYTHONPATH="")
    _assert_no_result(out)


def test_bench_refuses_cpu():
    out = _run([os.path.join(ROOT, "bench.py")])
    _assert_no_result(out)
    assert "no TPU" in out.stderr


_PROBE = """
import sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
from rabit_tpu.utils import compile_cache
path = compile_cache.setup({root!r})
print(path)
print(jax.config.jax_compilation_cache_dir)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    if from_env:
        want = str(tmp_path / "cache")
        out = _run(["-c", _PROBE.format(root=ROOT)],
                   JAX_COMPILATION_CACHE_DIR=want)
    else:
        # a copy of the helper in a scratch checkout, so the repo's own
        # cache directory is not written by the test
        root = tmp_path / "checkout"
        shutil.copytree(os.path.join(ROOT, "rabit_tpu"), root / "rabit_tpu",
                        ignore=shutil.ignore_patterns("__pycache__"))
        want = str(root / ".jax_cache")
        out = _run(["-c", _PROBE.format(root=str(root))],
                   PYTHONPATH=str(root))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [want, want]
    assert os.listdir(want), "nothing was cached there"
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
