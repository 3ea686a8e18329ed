"""Test harness config: force an 8-device virtual CPU mesh so multi-chip
sharding paths compile and run without TPU hardware (the driver's
dryrun_multichip uses the same mechanism). Must run before jax imports."""

import os
import sys

# Something may import jax before this conftest runs. Backend
# initialization is lazy, so switching the platform to CPU via
# jax.config still works here as long as no jax computation ran yet.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402
import rabit_tpu  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ensure_native_built() -> None:
    """Build librabit_tpu_core.so if missing or stale, so the recovery /
    integration tiers always run (the reference's CI builds its C++
    library before every test run, scripts/travis_script.sh)."""
    from rabit_tpu.utils.native_build import ensure_built
    try:
        ensure_built(_ROOT)
    except RuntimeError as e:
        pytest.exit(str(e), returncode=3)


_ensure_native_built()


@pytest.fixture
def single_engine():
    """A fresh single-process engine for each test."""
    rabit_tpu.finalize()
    rabit_tpu.init([], engine="empty")
    yield
    rabit_tpu.finalize()
