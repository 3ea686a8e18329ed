"""The harness's own tests run on the CPU before anything imports JAX,
with the same eight virtual devices as the repo's tests/conftest.py, so
that one pytest session over both directories agrees on the device
count whichever conftest loads first (the four-chip cell takes the first
four; the rehearsal subprocesses get four of their own)."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
