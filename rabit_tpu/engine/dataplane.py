"""XLA data plane behind the robust native engine — the north-star
composition: collectives execute on the device mesh (ICI/DCN on TPU,
gloo on the CPU backend in tests) while the C++ host control plane keeps
consensus, result replay, prepare-skip, and checkpoint recovery
(the wrapper structure of the reference's AllreduceRobust around its
TryAllreduce data plane, allreduce_robust.cc:159-219).

Lifecycle: XLA collectives require fixed live membership — a dead
participant hangs the program (SURVEY §7 hard part #1). The tracker
therefore stamps every link-(re)registration batch with an ``epoch``;
the C++ engine passes the current epoch into every data-plane call. When
the epoch has advanced past the world this process last formed (a worker
died and everyone re-registered), the callback tears the JAX distributed
runtime down and re-forms it at the epoch's coordinator (rank 0's host +
a tracker-relayed fresh port). Because the robust protocol only executes
a collective when every rank is aligned at the same op (RecoverExec
returns "execute" only on a uniform consensus round), all live ranks
enter the re-formation together — no extra agreement round is needed.

Failure mapping: any exception here returns nonzero to C++, which treats
it like a link reset — reconnect (advancing the epoch), replay, retry.

APPLICATION STATE CONTRACT: re-forming the device world drops the old
XLA backend client (``clear_backends``), which invalidates every live
``jax.Array`` in the surviving process — not just the collective's
internals. Applications using the XLA data plane must keep model and
optimizer state host-resident (numpy; the ``rabit.allreduce`` API is
numpy-in/numpy-out for exactly this reason) or re-``device_put`` their
device state after an epoch advance. The ``on_world_reformed`` hook
(exposed via ``NativeEngine``) fires with the new epoch after each
re-formation so applications can restore device-resident state.

Why this manages the distributed runtime client/service directly instead
of ``jax.distributed.initialize``: the default client terminates the
whole process (LOG(FATAL), jaxlib client.h) when a peer's heartbeat
lapses or a disconnect RPC fails — one worker's death would take the
survivors with it, exactly what the robust engine exists to prevent. We
build the same client with heartbeat policing disabled,
``shutdown_on_destruction=False`` and ``recoverable=True`` (the service
then neither expects this task at shutdown barriers nor propagates its
disconnect to peers), and tear a world down with an explicit
``client.shutdown()`` to the tracker-hosted service — which is alive by
design even when peers are dead — because reference-dropping alone
leaves a C++ error-poll zombie that LOG(FATAL)s later (see _teardown).
"""

from __future__ import annotations

import contextlib
import ctypes
import os
import sys
import time
from typing import Callable, Optional

import numpy as np

from .. import telemetry
from ..ops.reducers import DTYPE_ENUM, OP_NAMES


def _require_private_api():
    """The data plane rides jaxlib private APIs
    (``jax._src.distributed.global_state`` plus the distributed-runtime
    client), necessary because the public ``jax.distributed.initialize``
    client LOG(FATAL)s the process on peer death (see module
    docstring); ``utils/jaxcompat.py`` owns the private import and
    kwargs. Check at construction — a jax upgrade that removed them
    must fail loudly here, not mid-recovery (VERDICT r2 weak #7)."""
    try:
        from jax._src.distributed import global_state  # noqa: F401
    except ImportError as e:
        raise RuntimeError(
            "rabit_tpu's XLA data plane requires jax private modules "
            "(jax._src.distributed) — verified against jax 0.9.0; "
            "this jax build lacks them") from e
    from ..utils import jaxcompat
    jaxcompat.distributed_runtime_module()

# C hook signature (native/include/rabit_tpu_c.h RbtDataPlaneFn)
DATAPLANE_CB = ctypes.CFUNCTYPE(
    ctypes.c_int, ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int,
    ctypes.c_int, ctypes.c_uint32, ctypes.c_void_p)

_ENUM_DTYPE = {v: k for k, v in DTYPE_ENUM.items()}


class XlaDataPlane:
    """Callable registered through RbtSetDataPlane. One instance per
    NativeEngine; owns the JAX distributed-world lifecycle."""

    def __init__(self, lib: ctypes.CDLL, init_timeout: int = 60) -> None:
        _require_private_api()
        self._lib = lib
        self._init_timeout = init_timeout
        self._formed_epoch: Optional[int] = None
        self._mesh = None
        self._rank = 0
        self._world = 1
        # Epoch-changed signal (ADVICE r2): re-forming the device world
        # drops the old backend client, which invalidates EVERY live jax
        # Array in this process — application state must be host-resident
        # (numpy) across collectives, or re-device_put after an epoch
        # advance. This hook fires after each re-formation so apps can
        # restore device state; NativeEngine.on_world_reformed exposes it.
        self.on_world_reformed: Optional[Callable[[int], None]] = None
        # test hook: script one callback failure on a healthy world
        # (RABIT_DATAPLANE_FAIL_AT=<invocation index>) to exercise the
        # device-plane-only failure -> kReset -> epoch re-formation path
        fail_at = os.environ.get("RABIT_DATAPLANE_FAIL_AT")
        self._fail_at: Optional[int] = int(fail_at) if fail_at else None
        self._invocations = 0
        # Self-healing retry rung (ISSUE 13): with
        # RABIT_COLLECTIVE_RETRIES=N > 0 a failed device collective is
        # re-run in place up to N times from a cached copy of its input
        # — the world is re-formed at the SAME epoch, C++ never sees the
        # failure, no rank is evicted. 0 (the default) preserves the
        # pre-ladder behavior exactly: first failure -> nonzero return
        # -> link reset escalation.
        retries = os.environ.get("RABIT_COLLECTIVE_RETRIES", "0")
        try:
            self._retries = max(0, int(retries))
        except ValueError as e:
            raise ValueError(
                f"RABIT_COLLECTIVE_RETRIES must be an integer, "
                f"got {retries!r}") from e
        # Python-plane retry count for the live /metrics gauge (the
        # native plane keeps its own counters behind RbtRecoveryStats)
        self.retries_total = 0
        # EQuARX-style wire quantization for ring-path float SUMs
        # (rabit_dataplane_wire spec, e.g. bf16 | int8 | int8:bf16@512):
        # compresses only the ppermute'd ICI bytes; accumulation stays
        # full-precision and all ranks end bit-identical (the
        # replay-buffer contract). Validated here even though dispatch
        # reads the env itself: a typo must not silently run
        # uncompressed while the user believes the wire is quantized.
        # Whether a requested wire actually engages is a
        # per-payload-size decision (rabit_dataplane_wire_mincount /
        # the dispatch table / adaptive election) made in
        # parallel/dispatch.py.
        wire = os.environ.get("RABIT_DATAPLANE_WIRE", "")
        if wire:
            from ..parallel.wire import canonical_wire as _canonical_wire
            try:
                wire = _canonical_wire(wire)
            except ValueError as e:
                raise ValueError(f"rabit_dataplane_wire: {e}") from None
        self._wire: Optional[str] = wire or None
        # allreduce algorithm override (rabit_reduce_method = auto |
        # tree | ring | bidir | swing); "auto" consults the measured
        # dispatch table per payload size
        from ..parallel.dispatch import METHODS
        method = os.environ.get("RABIT_REDUCE_METHOD", "") or "auto"
        if method != "auto" and method not in METHODS:
            raise ValueError(
                f"rabit_reduce_method must be one of "
                f"{('auto',) + METHODS}, got {method!r}")
        self._method = method
        # skew-adaptation knobs (rabit_skew_adapt / rabit_skew_preagg_ms
        # / rabit_skew_poll_ms): validated at init for the same reason as
        # the wire — a garbage value must fail loudly here, not silently
        # disable adaptation mid-training. The knobs themselves are read
        # live by telemetry/skew.py on each dispatch.
        from ..telemetry import skew as _skewmod
        _skewmod.preagg_ms_per_mib()   # raises ValueError on garbage
        _skewmod.poll_interval_s()     # raises ValueError on garbage
        _skewmod.sync_rounds()         # raises ValueError on garbage
        # keep the ctypes callback object alive for the C side
        self.c_callback = DATAPLANE_CB(self._invoke)

    # -- world lifecycle --------------------------------------------------
    def _coord_addr(self) -> str:
        buf = ctypes.create_string_buffer(256)
        ln = ctypes.c_size_t()
        rc = self._lib.RbtCoordAddr(buf, ctypes.byref(ln), 256)
        if rc != 0:
            raise RuntimeError("RbtCoordAddr failed")
        return buf.value.decode()

    def _teardown(self) -> None:
        import gc
        import jax
        self._mesh = None
        self._formed_epoch = None
        from jax._src.distributed import global_state
        client = global_state.client
        if client is not None:
            # Stop the agent EXPLICITLY. Dropping references is not
            # enough once a gloo backend was built on this client: a
            # C++-side reference keeps the error-poll thread alive as a
            # zombie, and whenever its (reaped or stopping) service
            # cancels the poll, client.h LOG(FATAL)s this process.
            # client.shutdown() cancels the poll and returns promptly —
            # recoverable tasks skip the peer barrier; the
            # tracker-hosted service it talks to outlives every worker
            # by design.
            try:
                client.shutdown()
            except Exception as e:  # noqa: BLE001 - service may be gone
                print(f"[dataplane] client disconnect: {e}",
                      file=sys.stderr, flush=True)
        del client
        global_state.client = None
        global_state.preemption_sync_manager = None
        global_state.process_id = 0
        global_state.num_processes = 1
        global_state.coordinator_address = None
        # compiled executables pin the PJRT client, which co-owns the
        # distributed-runtime client; clear them so the next trace binds
        # the new world's context
        jax.clear_caches()
        from jax.extend import backend as jax_backend
        jax_backend.clear_backends()
        # destroy (not merely unreference) whatever the caches held
        # before the ready ack races the tracker's service reaping
        gc.collect()

    def _form_world(self, epoch: int) -> None:
        import jax
        from jax._src.distributed import global_state

        from ..utils import jaxcompat
        # recovery accounting: a re-formation in a process that already
        # had a world means the epoch advanced under it (a peer died and
        # the fleet rewired); the span carries how long the device world
        # was down for this rank
        t0 = time.perf_counter()
        was_formed = self._formed_epoch is not None
        if was_formed:
            telemetry.count("recovery.epoch_advance",
                            provenance="recovery")
            from ..telemetry import events
            events.emit("recovery.epoch_advance",
                        f"rank {self._rank} re-forming at epoch {epoch}",
                        rank=self._rank)
        self._teardown()
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        self._rank = int(self._lib.RbtGetRank())
        self._world = int(self._lib.RbtGetWorldSize())
        addr = self._coord_addr()
        if addr.rsplit(":", 1)[-1] in ("", "0"):
            raise RuntimeError(
                "tracker did not provide a device-world coordinator "
                "(launch with coordinator hosting enabled — "
                "rabit_dataplane=xla in the worker command or "
                "RABIT_DATAPLANE=xla in the environment)")
        # huge heartbeat budget, on purpose: failure detection belongs
        # to the socket control plane. The jaxlib agent's watchdogs
        # (missed heartbeats, error polling) LOG(FATAL) the process —
        # one peer's death would take every survivor with it, the exact
        # failure the robust engine exists to absorb. A Python
        # missed_heartbeat_callback is no escape: invoking it aborts via
        # std::bad_cast in this jaxlib.
        # recoverable=True is load-bearing:
        # it marks the task recoverable in the coordination service,
        # which then does NOT propagate this task's disconnect as a
        # fatal error to peers still polling — without it, any
        # non-simultaneous client teardown (recovery, staggered process
        # exit) LOG(FATAL)s the laggards. jaxcompat builds and
        # connects the client.
        client = jaxcompat.connect_client(addr, self._rank,
                                          self._init_timeout)
        global_state.client = client
        global_state.process_id = self._rank
        global_state.num_processes = self._world
        global_state.coordinator_address = addr
        from jax.sharding import Mesh
        reps = {}
        for d in jax.devices():
            reps.setdefault(d.process_index, d)
        self._mesh = Mesh(np.array([reps[i] for i in sorted(reps)]),
                          ("proc",))
        self._formed_epoch = epoch
        # re-arm the skew agreement boundary: every process of the new
        # epoch passes through here before its first collective, so the
        # dispatch counters restart together and the first dispatch
        # re-agrees on a digest before anything adapts (ranks may have
        # been reassigned — the old agreed digest is dropped)
        from ..telemetry import skew as _skewmod
        _skewmod.reset_sync()
        telemetry.record_span("recovery.world_reform",
                              time.perf_counter() - t0,
                              provenance="recovery", epoch=epoch,
                              reformed=was_formed)
        if self.on_world_reformed is not None:
            self.on_world_reformed(epoch)

    def ensure_world(self, epoch: int) -> None:
        if self._formed_epoch != epoch or self._mesh is None:
            self._form_world(epoch)

    def shutdown(self) -> None:
        if self._formed_epoch is None:
            return
        self._teardown()

    @property
    def formed(self) -> bool:
        return self._formed_epoch is not None

    # -- the hook ---------------------------------------------------------
    def _invoke(self, buf_p, count, dtype, op, epoch, _ctx) -> int:
        if int(count) == 0 and int(op) < 0:
            # teardown sentinel from ReconnectLinks: the epoch advanced;
            # drop the old world's client NOW — before the ready ack —
            # so the tracker can reap old coordination services without
            # poisoning a live client
            try:
                if self.formed:
                    self._teardown()
            except Exception as e:  # noqa: BLE001 - must not unwind into C
                print(f"[dataplane] teardown sentinel failed: {e}",
                      file=sys.stderr, flush=True)
            return 0
        # The per-collective round id: the C++ robust layer drives every
        # rank through the same op sequence, so this counter is globally
        # aligned across ranks and makes the retry idempotent — every
        # attempt of round k re-runs the same reduction over the same
        # cached inputs, and the replay log never sees a partial result.
        round_id = self._invocations
        pristine: Optional[np.ndarray] = None
        buf: Optional[np.ndarray] = None
        attempt = 0
        while True:
            try:
                if self._fail_at is not None and \
                        round_id == self._fail_at:
                    self._fail_at = None  # fire exactly once
                    raise RuntimeError("scripted dataplane failure "
                                       "(RABIT_DATAPLANE_FAIL_AT)")
                if buf is None:
                    self._invocations += 1
                    dt = _ENUM_DTYPE[int(dtype)]
                    nbytes = int(count) * dt.itemsize
                    raw = np.ctypeslib.as_array(
                        ctypes.cast(buf_p, ctypes.POINTER(ctypes.c_uint8)),
                        shape=(nbytes,))
                    buf = raw.view(dt)
                    if self._retries > 0:
                        # cache the round's input so a retry reduces the
                        # SAME operands (buf is reduced in place)
                        pristine = buf.copy()
                self.ensure_world(int(epoch))
                self._allreduce(buf, int(op))
                if attempt > 0:
                    import zlib
                    from ..telemetry import flight
                    flight.note(
                        "recovery.retry",
                        f"rank {self._rank} round {round_id} recovered "
                        f"in-collective after {attempt} retr"
                        f"{'y' if attempt == 1 else 'ies'} "
                        f"crc={zlib.crc32(buf.tobytes()):08x}")
                return 0
            except Exception as e:  # noqa: BLE001 — must not unwind into C
                if attempt < self._retries:
                    # retry rung: restore the cached inputs, re-form the
                    # device world at the SAME epoch (no membership
                    # change, no eviction), back off, re-run the round
                    attempt += 1
                    self.retries_total += 1
                    telemetry.count("recovery.retry", op="dataplane",
                                    provenance="recovery")
                    from ..telemetry import events, flight
                    flight.note(
                        "recovery.retry",
                        f"rank {self._rank} round {round_id} attempt "
                        f"{attempt}/{self._retries}: "
                        f"{type(e).__name__}: {e}")
                    events.emit(
                        "recovery.retry",
                        f"rank {self._rank} round {round_id} attempt "
                        f"{attempt}/{self._retries}: {type(e).__name__}",
                        rank=self._rank)
                    print(f"[dataplane] rank {self._rank} round {round_id} "
                          f"retry {attempt}/{self._retries} after "
                          f"{type(e).__name__}: {e}",
                          file=sys.stderr, flush=True)
                    if pristine is not None and buf is not None:
                        np.copyto(buf, pristine)
                    try:
                        self._teardown()
                    except Exception:  # pragma: no cover - best-effort
                        pass
                    from ..utils.retry import backoff_delay
                    time.sleep(backoff_delay(attempt - 1))
                    continue
                print(f"[dataplane] rank {self._rank} epoch {epoch} failed: "
                      f"{type(e).__name__}: {e}", file=sys.stderr,
                      flush=True)
                # retries exhausted (or disabled): the nonzero return
                # becomes a link reset on the C++ side — count it under
                # recovery provenance so fleet tables show how many
                # collectives escalated past the retry rung
                telemetry.count("recovery.link_reset", op="dataplane",
                                provenance="recovery")
                from ..telemetry import events, flight
                flight.note("link_reset",
                            f"rank {self._rank} epoch {epoch}: "
                            f"{type(e).__name__}: {e}")
                events.emit("recovery.link_reset",
                            f"rank {self._rank} epoch {epoch}: "
                            f"{type(e).__name__}", rank=self._rank)
                try:
                    self._teardown()
                except Exception:  # pragma: no cover - best-effort
                    pass
                return 1

    def _allreduce(self, buf: np.ndarray, op: int) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..parallel.collectives import device_allreduce
        from ..parallel.dispatch import wire_mincount as _wire_mincount
        if self._world == 1:
            return
        mesh = self._mesh
        n = buf.size
        # span records the wire REQUEST alongside the payload; whether
        # the codec actually engaged at this size is the dispatch
        # counter's provenance row (resolve() inside device_allreduce)
        sp = telemetry.span(
            "dataplane.allreduce", nbytes=buf.nbytes,
            op=OP_NAMES.get(op, str(op)), method=self._method,
            wire_requested=os.environ.get("RABIT_DATAPLANE_WIRE", "")
            or "off",
            round=telemetry.collective_round("dataplane.allreduce"))
        # 64-bit payloads: without x64 device_put truncates to 32 bits
        if buf.dtype.itemsize == 8:
            ctx = jax.enable_x64(True)
        else:
            ctx = contextlib.nullcontext()
        with sp, ctx:
            sharding = NamedSharding(mesh, P("proc"))
            local = jax.device_put(buf.reshape(1, n), mesh.local_devices[0])
            xs = jax.make_array_from_single_device_arrays(
                (self._world, n), sharding, [local])
            if self._method == "hier":
                # phase-decomposed two-level schedule; the host grouping
                # comes from RABIT_HIER_GROUP (exported by the native
                # launcher from tracker topology, or set explicitly).
                # No phase_guard here: stall policing on this path is
                # the C++ control plane's watchdog around the whole
                # callback, and a failure in any phase returns nonzero
                # to C++ -> link reset -> replay, same as the flat path.
                from ..parallel.collectives import device_hier_allreduce
                wire = self._wire if (self._wire and n >= _wire_mincount()) \
                    else None
                out = device_hier_allreduce(xs, mesh, op, axis="proc",
                                            wire=wire)
            else:
                # wire="auto": the env-requested wire engages only at
                # sizes where measurement says it pays (explicit
                # per-call wire= in the collectives API still forces it)
                out = device_allreduce(xs, mesh, op, axis="proc",
                                       method=self._method, wire="auto")
            if sp.live:
                # label adapted rounds for cross-rank stitching (same
                # contract as the xla engine span)
                from ..telemetry import skew as _skewmod
                from ..parallel import dispatch as _dispatchmod
                tag = _skewmod.last_applied()
                if tag:
                    sp.attrs["adapted"] = tag
                # the wire OUTCOME next to the request above: what
                # dispatch actually resolved for this payload (gated,
                # adapted, or forced) — trace_report can then show
                # request vs outcome per round
                sp.attrs["wire_applied"] = _dispatchmod.last_wire() or "off"
            res = np.asarray(out.addressable_data(0)).reshape(-1)
        if res.dtype != buf.dtype:
            raise TypeError(
                f"device allreduce changed dtype {buf.dtype} -> {res.dtype}")
        np.copyto(buf, res)
