"""XLA engine — the TPU-native data plane behind the rabit host API.

Maps the reference's process-centric model onto JAX multi-process SPMD:
rank ↔ ``jax.process_index()`` and world ↔ ``jax.process_count()`` (the
tracker's rendezvous role is played by the JAX coordination service,
``jax.distributed.initialize`` — SURVEY §2.3). Each rank's host buffer is
staged onto its local device as one slice of a global ``[world, n]``
array sharded over a one-device-per-process mesh; the reduction runs as a
jitted XLA program whose cross-process collective rides ICI/DCN; the
replicated result is fetched back into the caller's buffer — preserving
the reference's in-place ``sendrecvbuf`` contract (engine.h:74-96).

Algorithm dispatch by element count generalizes the
``reduce_ring_mincount`` crossover (allreduce_base.h:532-534) the
reference documents but never wires: with ``rabit_reduce_method=auto``
(the default) each payload picks among {tree, ring, bidir, swing} — and
gates a requested quantized wire — from the measured table in
``parallel/dispatch.py``; an explicit ``rabit_reduce_ring_mincount``
pins the legacy two-way tree/ring split instead.

Fault tolerance note: this engine is the *data plane* only. XLA
collectives hang if a participant dies (SURVEY §7 hard parts); the robust
control plane (consensus, replay, recovery) lives host-side in the C++
engine and wraps this one.
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional, Tuple

import numpy as np

from .base import AllreduceHandle, Engine
from . import ckpt_store
from .. import telemetry
from ..telemetry import profile as _profile
from ..utils.config import Config
from ..utils import log
from ..utils.log import log_debug
from ..utils.watchdog import Watchdog


class XlaEngine(Engine):
    def __init__(self) -> None:
        self._rank = 0
        self._world = 1
        self._mesh = None
        self._cfg: Optional[Config] = None
        self._global: Optional[bytes] = None
        self._local: Optional[bytes] = None
        self._lazy: Optional[Callable[[], bytes]] = None
        self._version = 0
        self._ring_mincount: Optional[int] = None
        self._method = "auto"
        self._wire: Optional[str] = None
        self._wire_mincount = 0
        self._debug = False
        self._groups = None
        self._hier_scale = 1.0
        self._watchdog = Watchdog()  # disabled until init reads config
        self._store: Optional[ckpt_store.CheckpointStore] = None
        # live observability plane (off by default, see engine/native.py)
        self._metrics_server = None
        self._flight = None
        # async collective dispatch (ISSUE 11): lazily-built 1-worker
        # executor + in-flight futures; see _async_executor for why ONE
        self._async_ex = None
        self._async_pending: list = []

    def init(self, args: List[str]) -> None:
        import jax
        cfg = Config.from_args(args)
        self._cfg = cfg
        coord = cfg.get("rabit_coordinator")
        nproc = cfg.get_int("rabit_num_processes", 0)
        if coord and nproc > 1:
            # Multi-host bootstrap: the JAX coordination service is the
            # tracker (reference ConnectTracker, allreduce_base.cc:222-259).
            # Must run before anything touches the XLA backend, so the
            # already-initialized check inspects distributed state only.
            from jax._src.distributed import global_state
            if global_state.client is None:
                # cross-process collectives on the CPU backend need an
                # explicit implementation; without it psum/ppermute
                # silently reduce only the local shard (only the CPU
                # client reads this, so it is harmless on TPU/GPU)
                jax.config.update(
                    "jax_cpu_collectives_implementation", "gloo")
                jax.distributed.initialize(
                    coordinator_address=coord,
                    num_processes=nproc,
                    process_id=cfg.get_int("rabit_process_id", 0))
        self._rank = jax.process_index()
        self._world = jax.process_count()
        from ..parallel import dispatch as _dispatch
        # an explicit rabit_reduce_ring_mincount pins the legacy
        # two-way crossover; otherwise method="auto" consults the
        # measured dispatch table (parallel/dispatch.py)
        mincount = cfg.get("rabit_reduce_ring_mincount")
        self._ring_mincount = None if mincount is None else int(mincount)
        self._method = cfg.get("rabit_reduce_method", "auto") or "auto"
        if self._method != "auto" and self._method not in _dispatch.METHODS:
            raise ValueError(
                f"rabit_reduce_method must be one of "
                f"{('auto',) + _dispatch.METHODS}, got {self._method!r}")
        wire = cfg.get("rabit_dataplane_wire", "") or None
        if wire is not None:
            from ..parallel import wire as _wirespec
            try:
                wire = _wirespec.canonical_wire(wire)
            except ValueError as e:
                raise ValueError(
                    f"rabit_dataplane_wire: {e}") from None
        self._wire = wire
        self._wire_mincount = cfg.get_size(
            "rabit_dataplane_wire_mincount",
            _dispatch.WIRE_MINCOUNT_DEFAULT)
        # hierarchical schedule: resolve the host grouping once at init
        # (explicit rabit_hier_group spec beats the RABIT_HIER_GROUP env
        # the native launcher exports from tracker topology); per-phase
        # watchdog deadlines scale by rabit_hier_phase_deadline_scale —
        # each phase moves ~1/g (intra) or ~1/H (inter) of the flat
        # payload, so a deployment can tighten phases below the
        # whole-collective budget
        from ..parallel import topology as _topology
        self._groups = _topology.resolve_groups(
            self._world, spec=cfg.get("rabit_hier_group"))
        self._hier_scale = float(
            cfg.get("rabit_hier_phase_deadline_scale", 1.0) or 1.0)
        self._debug = cfg.get_bool("rabit_debug")
        log.set_debug(self._debug)
        log.set_identity(self._rank, self._world)
        telemetry.configure(cfg)
        _profile.configure(cfg)
        from ..parallel.collectives import configure_async
        configure_async(cfg)
        self._watchdog = Watchdog.from_config(cfg)
        self._start_live_plane(cfg)
        if self._world > 1:
            # formed identity for the `resume` handshake (ISSUE 10):
            # reconnecting pollers re-present it to a resumed tracker
            import os as _os
            from ..tracker import membership as _mship
            _mship.note_identity(
                _os.environ.get("RABIT_TASK_ID", str(self._rank)),
                self._rank, 0)
        ckpt_dir = cfg.get("rabit_ckpt_dir")
        if ckpt_dir:
            self._store = ckpt_store.CheckpointStore(
                ckpt_dir, rank=self._rank,
                keep=cfg.get_int("rabit_ckpt_keep", ckpt_store.DEFAULT_KEEP))
        if self._world > 1:
            self._mesh = self._build_mesh()

    def _build_mesh(self):
        """One representative device per process — the engine's 'world'
        ring. (Collectives over the full per-process device set belong to
        the rabit_tpu.parallel layer, not the host API.)"""
        import jax
        from jax.sharding import Mesh
        reps = {}
        for d in jax.devices():
            reps.setdefault(d.process_index, d)
        devs = [reps[i] for i in sorted(reps)]
        return Mesh(np.array(devs), ("proc",))

    def _start_live_plane(self, cfg) -> None:
        """Per-rank metrics endpoint + flight recorder (see
        engine/native.py — same knobs, same defaults-off contract)."""
        from ..telemetry import flight as _flight
        self._flight = _flight.FlightRecorder.from_config(cfg,
                                                          rank=self._rank)
        if "rabit_metrics_port" not in cfg:
            return
        from ..telemetry import live as _live
        try:
            self._metrics_server = _live.start_rank_server(
                cfg.get_int("rabit_metrics_port", 0), self._rank,
                self._world, gauges_fn=self._live_gauges)
        except OSError as e:
            log.log_warn("metrics endpoint failed to start: %s", e)
            return
        if self._world > 1:
            _live.announce_endpoint(self._metrics_server.host,
                                    self._metrics_server.port, self._rank)

    def _live_gauges(self):
        from ..telemetry import slo as _slo
        return [
            ("rabit_watchdog_expired_total",
             "Watchdog deadline expiries in this process.", "counter",
             [({}, self._watchdog.expired_total)]),
            # per-rank SLO burn: this rank's p99 collective latency
            # judged against the fleet objective (telemetry/slo.py)
            *_slo.rank_gauges(),
        ]

    def _hier_phase_guard(self, name: str, nbytes: int):
        """Per-phase watchdog deadline for the hierarchical schedule:
        the usual payload-proportional deadline, scaled by
        ``rabit_hier_phase_deadline_scale`` (phases move a fraction of
        the flat payload, so <1 tightens them; disabled watchdog still
        yields the shared no-op guard)."""
        from ..utils.watchdog import scale_deadline_s
        d = scale_deadline_s(nbytes, self._watchdog.floor_ms,
                             self._watchdog.ms_per_mb) * self._hier_scale
        return self._watchdog.guard(name, nbytes=nbytes, deadline_s=d)

    def epoch_reset(self, world: int) -> None:
        """Elastic-membership epoch hook (lint rule R002): adopt a
        resized world and drop every piece of state derived from the
        old one. For this engine a resize always arrives through a
        fresh registration (the JAX distributed client is bound to one
        coordination service per process lifetime), so the hook's job
        is the state that OUTLIVES registration: host grouping, the
        skew plane's agreed digest and dispatch counter, the dispatch
        table cache, and the checkpoint store — whose newest old-world
        version is pinned against pruning until the new world commits
        its first checkpoint, and which a re-admitted joiner seeds
        from its siblings' durable shards."""
        from ..parallel import dispatch as _dispatch
        from ..parallel import topology as _topology
        from ..telemetry import flight as _fl
        from ..telemetry import skew as _skew
        from ..tracker import membership as _membership
        world = int(world)
        old, self._world = self._world, world
        _topology.epoch_reset(world)
        _dispatch.epoch_reset(world)
        _skew.epoch_reset(world)
        _membership.epoch_reset(world)
        self._groups = _topology.resolve_groups(world)
        log.set_identity(self._rank, world)
        if self._store is not None:
            self._store.protect_current()
            self._store.adopt_latest_from_peers()
        telemetry.count("membership.epoch_reset",
                        provenance="membership")
        telemetry.record_span("membership.transition", 0.0, op="resize",
                              provenance="membership", old_world=old,
                              world=world)
        _fl.note("member_resize", f"world {old} -> {world}")
        from ..telemetry import events
        events.emit("membership.epoch_reset",
                    f"world {old} -> {world}", rank=self._rank)

    def shutdown(self) -> None:
        try:
            self._drain_async()
        finally:
            if self._async_ex is not None:
                self._async_ex.shutdown(wait=True)
                self._async_ex = None
        if self._metrics_server is not None:
            self._metrics_server.stop()
            self._metrics_server = None
        if self._flight is not None:
            self._flight.uninstall()
            self._flight = None
        _profile.stop_poller()
        telemetry.export_at_shutdown(self._rank, self._world)

    # -- collectives ------------------------------------------------------
    def _resolve_method_wire(self, n: int):
        method = self._method
        if method == "auto" and self._ring_mincount is not None:
            method = "ring" if n >= self._ring_mincount else "tree"
        # a configured wire engages only above the size gate
        # (rabit_dataplane_wire_mincount); below it the payload runs
        # unquantized — wire loses wall-clock there AND costs accuracy
        wire = self._wire if (self._wire and n >= self._wire_mincount) \
            else None
        return method, wire

    def _allreduce_device(self, buf: np.ndarray, op: int, method: str,
                          wire: Optional[str], sp=None) -> None:
        """The device half of :meth:`allreduce`: stage, reduce, fetch,
        copy back in place. Shared verbatim by the sync path (under its
        span + watchdog) and the async worker (whose span is recorded
        at ``wait()`` with the exposed/overlapped split)."""
        import contextlib
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..parallel.collectives import device_allreduce
        n = buf.size
        mesh = self._mesh
        # 64-bit payloads: without x64, device_put silently truncates
        # int64/float64 to 32 bits; scope-enable it for this reduction
        if buf.dtype.itemsize == 8:
            ctx = jax.enable_x64(True)
        else:
            ctx = contextlib.nullcontext()
        with ctx:
            sharding = NamedSharding(mesh, P("proc"))
            local = jax.device_put(buf.reshape(1, n), mesh.local_devices[0])
            xs = jax.make_array_from_single_device_arrays(
                (self._world, n), sharding, [local])
            if method == "hier":
                # phase-decomposed composition: reduce-scatter /
                # inter-host / allgather run as separate programs so the
                # watchdog polices each phase at its own (scaled) budget
                from ..parallel.collectives import device_hier_allreduce
                out = device_hier_allreduce(
                    xs, mesh, op, axis="proc", groups=self._groups,
                    wire=wire, phase_guard=self._hier_phase_guard)
            else:
                out = device_allreduce(xs, mesh, op, axis="proc",
                                       method=method, wire=wire)
            if sp is not None and sp.live:
                # round-carrying span learns which adaptation the device
                # layer applied (if any) so cross-rank stitching can
                # label adapted rounds (telemetry/skew.py)
                from ..telemetry import skew as _skewmod
                tag = _skewmod.last_applied()
                if tag:
                    sp.attrs["adapted"] = tag
            res = np.asarray(out.addressable_data(0)).reshape(-1)
        if res.dtype != buf.dtype:
            raise TypeError(
                f"device allreduce changed dtype {buf.dtype} -> {res.dtype}")
        np.copyto(buf, res)

    def allreduce(self, buf: np.ndarray, op: int,
                  prepare_fun: Optional[Callable[[], None]] = None,
                  key: str = "") -> None:
        if prepare_fun is not None:
            prepare_fun()
        if self._world == 1:
            return
        self._drain_async()
        from ..ops.reducers import OP_NAMES
        n = buf.size
        method, wire = self._resolve_method_wire(n)
        sp = telemetry.span("engine.allreduce", nbytes=buf.nbytes,
                            op=OP_NAMES.get(op, str(op)), method=method,
                            wire=wire,
                            round=telemetry.collective_round(
                                "engine.allreduce"))
        wd = self._watchdog.guard("engine.allreduce", nbytes=buf.nbytes)
        with wd, sp:
            self._allreduce_device(buf, op, method, wire, sp=sp)
        log_debug("xla allreduce n=%d op=%d method=%s", n, op, method)

    def allreduce_async(self, buf: np.ndarray, op: int,
                        prepare_fun: Optional[Callable[[], None]] = None,
                        key: str = "") -> AllreduceHandle:
        """Issue the allreduce on the dispatch thread and return an
        awaitable handle; the caller's thread is free to compute the
        next bucket while this one rides the wire. The watchdog guard
        arms NOW and disarms when the op completes (or fails), so every
        in-flight op keeps its deadline. ``buf`` must be left alone
        until ``wait()`` returns it."""
        if prepare_fun is not None:
            prepare_fun()
        if self._world == 1:
            return AllreduceHandle(value=buf)
        from ..ops.reducers import OP_NAMES
        n = buf.size
        method, wire = self._resolve_method_wire(n)
        opname = OP_NAMES.get(op, str(op))
        nbytes = buf.nbytes
        rnd = telemetry.collective_round("engine.allreduce")
        telemetry.count("async.issued", nbytes=nbytes, op=opname,
                        method=method, wire=wire, provenance="engine")
        guard = self._watchdog.guard("engine.allreduce", nbytes=nbytes)
        guard.__enter__()
        t_issue = time.perf_counter()

        def task():
            try:
                self._allreduce_device(buf, op, method, wire)
            finally:
                guard.__exit__(None, None, None)

        with telemetry.span("engine.allreduce.issue", nbytes=nbytes,
                            op=opname, method=method, wire=wire,
                            round=rnd):
            fut = self._async_executor().submit(task)
        self._async_pending.append(fut)

        def wait_fn():
            t_wait = time.perf_counter()
            try:
                fut.result()
            finally:
                try:
                    self._async_pending.remove(fut)
                except ValueError:
                    pass
            t_done = time.perf_counter()
            exposed = t_done - t_wait
            overlapped = max(0.0, (t_done - t_issue) - exposed)
            telemetry.record_span(
                "engine.allreduce", t_done - t_issue, nbytes=nbytes,
                op=opname, method=method, wire=wire, provenance="engine",
                **{"round": rnd, "async": 1,
                   "wire_exposed_ms": exposed * 1e3,
                   "wire_overlapped_ms": overlapped * 1e3})
            _profile.record_overlap("engine.allreduce", method, exposed,
                                    overlapped)
            log_debug("xla async allreduce n=%d op=%d method=%s",
                      n, op, method)
            return buf

        return AllreduceHandle(wait_fn=wait_fn, ready_fn=fut.done)

    def _async_executor(self):
        """ONE worker on purpose: a FIFO queue makes async issue order
        == device collective order in every process, so uniformly
        programmed ranks keep tracing one global schedule — concurrent
        workers could reorder collectives differently per rank and
        deadlock the fabric."""
        if self._async_ex is None:
            from concurrent.futures import ThreadPoolExecutor
            self._async_ex = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="rabit-async")
        return self._async_ex

    def _drain_async(self) -> None:
        """Fence before any synchronous collective: every process must
        observe one global collective order, so sync ops wait out the
        async queue first. Failures propagate here (fail fast) and
        again from the failed handle's own ``wait()``."""
        while self._async_pending:
            fut = self._async_pending[0]
            try:
                fut.result()
            finally:
                try:
                    self._async_pending.remove(fut)
                except ValueError:
                    pass

    def reduce_scatter(self, buf: np.ndarray, op: int) -> np.ndarray:
        """True ring reduce-scatter on the device mesh: ships 1/p of
        the allreduce bytes and returns only this rank's chunk (base.py
        documents the ownership layout)."""
        if self._world == 1:
            return buf.copy()
        self._drain_async()
        if buf.size % self._world:
            raise ValueError(
                f"reduce_scatter payload of {buf.size} elements must "
                f"divide by the world size {self._world}")
        from ..parallel.collectives import device_reduce_scatter
        from ..ops.reducers import OP_NAMES
        with telemetry.span("engine.reduce_scatter", nbytes=buf.nbytes,
                            op=OP_NAMES.get(op, str(op)), method="ring",
                            round=telemetry.collective_round(
                                "engine.reduce_scatter")), \
                self._watchdog.guard("engine.reduce_scatter",
                                     nbytes=buf.nbytes):
            out = self._device_collective(
                buf, lambda xs, mesh: device_reduce_scatter(
                    xs, mesh, op, axis="proc"))
        return out

    def allgather(self, buf: np.ndarray) -> np.ndarray:
        """True ring all-gather on the device mesh (no reduction
        arithmetic, p-1 neighbor hops)."""
        if self._world == 1:
            return buf.reshape(-1).copy()
        self._drain_async()
        from ..parallel.collectives import device_allgather
        nbytes = buf.nbytes * self._world
        with telemetry.span("engine.allgather", nbytes=nbytes,
                            method="ring",
                            round=telemetry.collective_round(
                                "engine.allgather")), \
                self._watchdog.guard("engine.allgather", nbytes=nbytes):
            out = self._device_collective(
                buf, lambda xs, mesh: device_allgather(
                    xs, mesh, axis="proc"))
        return out

    def _device_collective(self, buf: np.ndarray, fn) -> np.ndarray:
        """Stage a host buffer as one row of the [world, n] mesh array,
        run ``fn(xs, mesh)``, and fetch this rank's addressable shard
        (the same staging as :meth:`allreduce`, including the x64
        scope-enable for 8-byte dtypes)."""
        import contextlib
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        if buf.dtype.itemsize == 8:
            ctx = jax.enable_x64(True)
        else:
            ctx = contextlib.nullcontext()
        mesh = self._mesh
        n = buf.size
        with ctx:
            sharding = NamedSharding(mesh, P("proc"))
            local = jax.device_put(buf.reshape(1, n), mesh.local_devices[0])
            xs = jax.make_array_from_single_device_arrays(
                (self._world, n), sharding, [local])
            out = fn(xs, mesh)
            res = np.asarray(out.addressable_data(0)).reshape(-1)
        if res.dtype != buf.dtype:
            raise TypeError(
                f"device collective changed dtype {buf.dtype} -> "
                f"{res.dtype}")
        return res

    def broadcast(self, data: Optional[bytes], root: int) -> bytes:
        if self._world == 1:
            if data is None:
                raise ValueError(
                    "single-process broadcast must originate data")
            return data
        # Two phases like the reference binding (rabit.py:171-206):
        # 1) agree on length (tiny MAX allreduce), 2) ship payload.
        nlen = np.zeros(1, dtype=np.int64)
        if self._rank == root:
            nlen[0] = len(data)
        from ..ops.reducers import MAX as OP_MAX
        self.allreduce(nlen, OP_MAX)
        size = int(nlen[0])
        payload = np.zeros(size, dtype=np.uint8)
        if self._rank == root:
            payload[:] = np.frombuffer(data, dtype=np.uint8)
        with telemetry.span("engine.broadcast", nbytes=size, root=root,
                            round=telemetry.collective_round(
                                "engine.broadcast")):
            self._device_bcast(payload, root)
        return payload.tobytes()

    def _device_bcast(self, buf: np.ndarray, root: int) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..parallel.collectives import device_broadcast
        mesh = self._mesh
        n = buf.size
        sharding = NamedSharding(mesh, P("proc"))
        local = jax.device_put(buf.reshape(1, n), mesh.local_devices[0])
        xs = jax.make_array_from_single_device_arrays(
            (self._world, n), sharding, [local])
        out = device_broadcast(xs, mesh, root=root, axis="proc")
        np.copyto(buf, np.asarray(out.addressable_data(0)).reshape(-1))

    # -- checkpointing ----------------------------------------------------
    # In-memory, version-prefixed, like the reference's global_checkpoint
    # string (allreduce_robust.cc:443-451). Replay/recovery semantics are
    # provided by the robust C++ engine; here checkpoints make single- and
    # healthy-multi-process runs resumable in-process — and, with
    # ``rabit_ckpt_dir``, across process restarts via the durable store.
    def load_checkpoint(self, with_local: bool = False
                        ) -> Tuple[int, Optional[bytes], Optional[bytes]]:
        self._materialize_lazy()
        if self._version == 0 and self._store is not None:
            self._cold_restart(with_local)
        return (self._version, self._global, self._local)

    def _cold_restart(self, with_local: bool) -> None:
        """Fresh process with a durable store: resume from the newest
        stored version the world agrees on (doc/fault_tolerance.md).
        Single-process loads its own newest; multi-process runs the
        MAX-version / MIN-holder / broadcast consensus so every rank
        resumes the SAME version even when some ranks' disks lag."""
        store = self._store
        mine = store.latest_version()
        if self._world == 1:
            got = store.latest()
            if got is None:
                return
            self._version, self._global = got[0], got[1]
            self._local = got[2] or None
            return
        from ..ops.reducers import MAX as OP_MAX, MIN as OP_MIN
        word = np.array([mine], dtype=np.int64)
        self.allreduce(word, OP_MAX)
        maxv = int(word[0])
        if maxv <= 0:
            return
        word[0] = self._rank if mine >= maxv else self._world
        self.allreduce(word, OP_MIN)
        root = int(word[0])
        payload = None
        if self._rank == root:
            got = store.load(maxv)
            payload = got[0] if got is not None else b""
        self._global = self.broadcast(payload, root)
        self._version = maxv
        if with_local:
            got = store.load(maxv)  # local state never leaves the rank
            self._local = (got[1] or None) if got is not None else None
        telemetry.count("recovery.cold_restart",
                        nbytes=len(self._global), provenance="recovery")
        from ..telemetry import events
        events.emit("recovery.cold_restart",
                    f"resumed at checkpoint version {maxv} "
                    f"(holder rank {root})", rank=self._rank)

    def checkpoint(self, global_bytes: bytes,
                   local_bytes: Optional[bytes] = None) -> None:
        self._global = global_bytes
        self._local = local_bytes
        self._lazy = None
        self._version += 1
        if self._store is not None:
            self._store.save(self._version, global_bytes,
                             local_bytes or b"")

    def lazy_checkpoint(self, make_global: Callable[[], bytes]) -> None:
        self._lazy = make_global
        self._local = None
        self._version += 1

    def _materialize_lazy(self) -> None:
        if self._lazy is not None:
            self._global = self._lazy()
            self._lazy = None
            if self._store is not None:
                self._store.save(self._version, self._global)

    # -- properties -------------------------------------------------------
    @property
    def rank(self) -> int:
        return self._rank

    @property
    def world_size(self) -> int:
        return self._world
