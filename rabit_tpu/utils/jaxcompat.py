"""Adapters for jaxlib's private distributed-runtime bindings.

The device plane and the tracker's device-world coordinator ride the
*private* distributed runtime (service + client) because the public
``jax.distributed.initialize`` client LOG(FATAL)s the whole process on
peer death — exactly the failure the robust engine exists to absorb
(engine/dataplane.py module docstring). jax 0.9 exposes the bindings at
``jax._src.lib._jax``; the client's ``recoverable`` flag stops the
service from propagating one task's disconnect to its peers.

Liveness detection is effectively OFF: that job belongs to the socket
control plane, whose watchdog can report-and-recover instead of
aborting. This module keeps the private import and the kwargs in one
place, and fails at setup when a jaxlib lacks them.
"""

from __future__ import annotations

# effectively-never heartbeat budget (seconds): jaxlib's own watchdogs
# must never fire before the control plane's
_NEVER_S = 1 << 20


def distributed_runtime_module():
    """The module holding ``get_distributed_runtime_service`` /
    ``_client``. Raises RuntimeError with a pinning hint when a name is
    missing — fail at setup, not mid-recovery."""
    from jax._src.lib import _jax as mod
    for name in ("get_distributed_runtime_service",
                 "get_distributed_runtime_client"):
        if not hasattr(mod, name):
            import jaxlib
            raise RuntimeError(
                f"jaxlib private API {name!r} is missing in jaxlib "
                f"{getattr(jaxlib, '__version__', '?')} — the device "
                "plane's coordination contract is verified against "
                "jaxlib 0.9.0; pin jaxlib or run without "
                "rabit_dataplane=xla")
    return mod


def start_service(addr: str, num_nodes: int):
    """Start a coordination service with liveness detection disabled
    and a short shutdown grace (failure detection is the socket control
    plane's job, not the service's)."""
    return distributed_runtime_module().get_distributed_runtime_service(
        addr, num_nodes, heartbeat_timeout=_NEVER_S, shutdown_timeout=1)


def connect_client(addr: str, rank: int, init_timeout: int):
    """Build and connect a coordination client with the same
    never-abort posture. ``recoverable=True`` marks the task recoverable
    so the service does not propagate this task's disconnect as a fatal
    error to peers still polling."""
    client = distributed_runtime_module().get_distributed_runtime_client(
        addr, rank,
        init_timeout=init_timeout,
        heartbeat_timeout=_NEVER_S,
        shutdown_on_destruction=False,
        use_compression=True,
        recoverable=True)
    client.connect()
    return client
