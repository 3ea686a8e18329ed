"""Collective algorithms over a device mesh.

Capability parity with the reference's engine (allreduce_base.cc),
re-designed for XLA/ICI:

- ``tree_allreduce``   ↔ TryAllreduceTree (.cc:475-640) — delegated to
  ``lax.psum``/``pmax``/``pmin``, which XLA lowers to torus-optimal
  reductions over ICI (better than any hand-rolled tree on TPU).
- ``ring_reduce_scatter`` ↔ TryReduceScatterRing (.cc:829-918)
- ``ring_all_gather``     ↔ TryAllgatherRing (.cc:751-815)
- ``ring_allreduce``      ↔ TryAllreduceRing = RS + AG (.cc:930-949)
  expressed as explicit ``lax.ppermute`` neighbor exchanges — the ICI
  analogue of the reference's TCP ring, and the building block the
  sequence-parallel/ring-attention demos reuse.
- ``bcast_from_root``     ↔ TryBroadcast (.cc:649-737) — mask + psum.
- ``bidir_ring_allreduce``: two counter-rotating rings each carrying
  half the payload — doubles link utilization on a 1-D mesh where each
  ICI/TCP link is full-duplex.
- ``swing_allreduce``: the Swing recursive-distance schedule
  (arXiv:2401.09356) — log2(p) steps whose hop distances follow
  1,1,3,5,11,… so consecutive steps never reuse a link direction;
  power-of-two worlds only (falls back to the ring otherwise).
- ``hier_allreduce``: two-level topology-aware allreduce (ROADMAP open
  item 4) — intra-host ring reduce-scatter, inter-host ring/swing
  allreduce of the reduced shards across per-slot rings (the
  host-delegate fabric, ``parallel/topology.py``), then intra-host
  all-gather. Expressed as a composition of the grouped RS/AG
  primitives (every ``ring_*``/``swing_*`` schedule takes
  ``groups=`` and runs over disjoint sub-rings concurrently), so with
  g ranks per host the slow inter-host links carry 2n(H-1)/(gH)
  bytes instead of the flat ring's 2n(p-1)/p.
- ``device_reduce_scatter`` / ``device_allgather``: the two halves as
  first-class public collectives (arXiv:2112.01075 argues they are the
  substrate redistribution workloads compose from), span-instrumented
  and cost-stamped like ``device_allreduce``.
- ``device_allreduce`` dispatches {tree, ring, bidir, swing} and the
  wire per payload size from the measured table in
  ``parallel/dispatch.py`` — the ``reduce_ring_mincount`` crossover the
  reference documents but never dispatches (allreduce_base.h:532-534,
  SURVEY §2 #3), generalized from one constant to a sweep artifact.
- ``bucket_allreduce`` / ``device_allreduce_tree``: DDP-style gradient
  bucketing — a pytree flattens into one contiguous buffer per dtype so
  a training step issues one large dispatched collective instead of one
  small tree-path collective per parameter leaf.

All ``ring_*``/``tree_*``/``bcast_*`` functions are *per-shard* functions:
call them inside ``shard_map`` (or any SPMD context with a named axis).
``device_*`` functions are host-level conveniences that wrap shard_map.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
import time
import warnings
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import telemetry
from ..telemetry import profile as _profile
from ..telemetry import skew as _skew
from ..ops.reducers import SUM, MAX, MIN, BITOR, OP_NAMES, jax_reduce_fn
from . import topology as _topology
from .dispatch import (RING_MINCOUNT_DEFAULT,  # noqa: F401  (re-export)
                       WIRE_MINCOUNT_DEFAULT, resolve as _dispatch_resolve)

from jax import shard_map as _shard_map


def shard_map(f, **kwargs):
    """``jax.shard_map`` with the varying-manual-axes (replication)
    checker ON — the default for every sharded program in this library.
    The checker statically verifies that values declared replicated
    (``P()`` out_specs) really are, catching the double-psum bug class
    ``psum_identity_grad``'s docstring describes."""
    kwargs.setdefault("check_vma", True)
    return _shard_map(f, **kwargs)


def unchecked_shard_map(f, **kwargs):
    """``jax.shard_map`` with the replication checker OFF — for bodies
    built on ppermute ring chains (``ring_*`` collectives, ring
    attention, pipeline stages): their outputs are replicated by
    protocol, which the static checker cannot infer through a ppermute
    chain. Scope of use is exactly those bodies; everything else goes
    through :func:`shard_map`."""
    kwargs.setdefault("check_vma", False)
    return _shard_map(f, **kwargs)

def axis_size(axis_name) -> int:
    """Static size of the named mesh axis, as a Python int. Every
    Python-level schedule below (ring step counts, Swing tables) needs
    this as a concrete loop bound."""
    return lax.axis_size(axis_name)


def _ring_perm(p: int, reverse: bool = False):
    """next-neighbor ring permutation (reference ring_next link,
    allreduce_base.cc:433-435); ``reverse`` rotates the other way (the
    second ring of ``bidir_ring_allreduce``)."""
    if reverse:
        return [(i, (i - 1) % p) for i in range(p)]
    return [(i, (i + 1) % p) for i in range(p)]


def _group_tables(groups, p: int):
    """Static tables for grouped (sub-ring) schedules: ``groups`` must
    partition ``range(p)`` into equal-size rings (SPMD: every rank runs
    the identical program, so every sub-ring must have the same length
    and chunk shape). Returns ``(size, local_of)`` where ``size`` is the
    common ring length and ``local_of[rank]`` is the rank's position
    around its own ring."""
    flat = [r for grp in groups for r in grp]
    if sorted(flat) != list(range(p)):
        raise ValueError(
            f"groups {groups!r} must partition ranks 0..{p - 1}")
    sizes = {len(grp) for grp in groups}
    if len(sizes) != 1:
        raise ValueError(
            f"grouped schedules need uniform group sizes, got {groups!r} "
            "(SPMD runs one program on every rank; ragged groups would "
            "need per-rank chunk shapes)")
    local_of = [0] * p
    for grp in groups:
        for j, r in enumerate(grp):
            local_of[r] = j
    return next(iter(sizes)), tuple(local_of)


def _group_ring_perm(groups, reverse: bool = False):
    """Union of next-neighbor permutations over every sub-ring — one
    ppermute moves all groups' rings concurrently."""
    perm = []
    for grp in groups:
        g = len(grp)
        for j, r in enumerate(grp):
            perm.append((r, grp[(j - 1) % g] if reverse else
                         grp[(j + 1) % g]))
    return perm


# Wire-quantization for the ring collectives (EQuARX-style: the
# accumulator stays full-precision on-device; only the ppermute'd bytes
# are compressed — arXiv:2506.17615 does this inside XLA for TPU
# allreduce). The codec and the "<rs>[:<ag>][@<block>]" spec grammar
# live in parallel/wire.py; "bf16" halves ICI bytes, "int8"
# block-scales to ~1/4 with an f32 max-abs scale per block.
from .wire import (WIRE_BLOCK_DEFAULT, wire_block,  # noqa: F401 (re-export)
                   parse_wire as _parse_wire,
                   format_wire as _format_wire,
                   canonical_wire as _canonical_wire)
from .wire import encode as _codec_encode, decode as _codec_decode

# Back-compat alias: the pre-spec codec hard-wired one block size; the
# live value is now the spec's ``@block`` (default WIRE_BLOCK_DEFAULT,
# env rabit_wire_block via canonical_wire).
_INT8_BLOCK = WIRE_BLOCK_DEFAULT


def _normalize_wire(wire, op: int, dtype, chunk_len=None):
    """One policy for wire eligibility, used by every ring entry point:
    quantized wire applies only to float SUM payloads; int8 phases need
    the per-rank chunk to tile into scaling blocks (else degrade that
    phase to bf16). ``chunk_len=None`` skips the block check — for
    callers that pad the chunk up to a block multiple themselves
    (ring_allreduce). Returns the canonical spec string or None."""
    if wire is None:
        return None
    rs, ag, block = _parse_wire(wire)  # raises on malformed specs
    if op != SUM or not jnp.issubdtype(dtype, jnp.floating):
        return None
    if chunk_len is not None and chunk_len % block != 0:
        rs = "bf16" if rs == "int8" else rs
        ag = "bf16" if ag == "int8" else ag
    return _format_wire(rs, ag, block)


def _wire_pad_mult(wire, size: int) -> int:
    """Chunk-alignment multiple for pad-and-slice entry points: any
    int8 phase needs the per-rank chunk to tile into scaling blocks."""
    if not wire:
        return size
    rs, ag, block = _parse_wire(wire)
    return size * block if "int8" in (rs, ag) else size


def _wire_encode(x, wire: str):
    """Whole-payload encode under ``wire``'s RS codec (back-compat
    shim for tools; schedule code uses the per-phase codec directly)."""
    rs, _, block = _parse_wire(wire)
    return _codec_encode(x, rs, block)


def _wire_decode(enc, wire: str, shape):
    rs, _, block = _parse_wire(wire)
    return _codec_decode(enc, rs, shape)


def ring_reduce_scatter(x: jax.Array, axis_name: str, op: int = SUM,
                        wire: str | None = None,
                        reverse: bool = False,
                        groups=None) -> jax.Array:
    """Ring reduce-scatter: every rank contributes ``x`` (length n,
    divisible by axis size p) and ends owning chunk ``rank`` (length n/p)
    fully reduced. p-1 ppermute steps, each moving n/p elements — the
    bandwidth-optimal schedule the reference implements over TCP
    (allreduce_base.cc:829-918).

    ``wire`` compresses the ppermute'd bytes only (accumulation stays in
    the input dtype): "bf16" (~2x fewer ICI bytes, ~1e-2 rel err over a
    ring) or "int8" (block-scaled, ~4x, SUM only).

    ``reverse`` runs the mirror schedule around the counter-rotating
    ring; ownership still lands on chunk == rank.

    ``groups`` (a static tuple of equal-size rank tuples partitioning
    the axis) runs the same schedule over every sub-ring concurrently:
    each rank reduces only with its own group and ends owning chunk
    ``local index`` of the g-way split, reduced over its group — the
    intra-host phase of :func:`hier_allreduce`."""
    if x.ndim != 1:
        raise ValueError(
            f"ring_reduce_scatter takes a 1-D per-shard array, got "
            f"shape {x.shape}; flatten first")
    p = axis_size(axis_name)
    if groups is None:
        size, pos = p, lax.axis_index(axis_name)
        perm = _ring_perm(p, reverse)
    else:
        size, local_of = _group_tables(groups, p)
        pos = jnp.asarray(local_of)[lax.axis_index(axis_name)]
        perm = _group_ring_perm(groups, reverse)
    if size == 1:
        return x
    wire = _normalize_wire(wire, op, x.dtype, x.shape[0] // size)
    rs_codec, _, blk = _parse_wire(wire) if wire else (None, None, 0)
    combine = jax_reduce_fn(op)
    idx = pos
    # EQuARX hop contract: with a quantized wire, every received
    # contribution decodes to f32 and FOLDS in f32 — quantization error
    # enters once per hop at the wire, never compounds through a
    # low-precision accumulator. Cast back to the input dtype only at
    # the end (identity for f32 payloads).
    acc_dtype = jnp.float32 if rs_codec else x.dtype
    chunks = x.reshape(size, -1).astype(acc_dtype)
    # Schedule: at step s, send chunk (idx-s-1) mod p (accumulated so
    # far), receive into chunk (idx-s-2) mod p; after p-1 steps rank i
    # owns chunk i. (Offset chosen so ownership lands on chunk==rank,
    # unlike the classic (i+1) mod p formulation.) The reverse ring
    # mirrors the offsets: send (idx+s+1), receive into (idx+s+2).
    # Grouped runs are identical with p -> group size and rank -> the
    # rank's position around its own sub-ring.
    for step in range(size - 1):
        if reverse:
            send_i = (idx + step + 1) % size
            recv_i = (idx + step + 2) % size
        else:
            send_i = (idx - step - 1) % size
            recv_i = (idx - step - 2) % size
        send = lax.dynamic_index_in_dim(chunks, send_i, 0, keepdims=False)
        if rs_codec is None:
            got = lax.ppermute(send, axis_name, perm)
        else:
            enc = _codec_encode(send, rs_codec, blk)
            enc = tuple(lax.ppermute(e, axis_name, perm) for e in enc)
            got = _codec_decode(enc, rs_codec, send.shape)
        cur = lax.dynamic_index_in_dim(chunks, recv_i, 0, keepdims=False)
        chunks = lax.dynamic_update_index_in_dim(
            chunks, combine(cur, got), recv_i, 0)
    mine = lax.dynamic_index_in_dim(chunks, idx, 0, keepdims=False)
    return mine.astype(x.dtype)


def ring_all_gather(x: jax.Array, axis_name: str,
                    wire: str | None = None,
                    reverse: bool = False,
                    groups=None) -> jax.Array:
    """Ring all-gather: rank i contributes chunk ``x`` (length m) and all
    ranks end with the concatenation [p*m] in rank order
    (TryAllgatherRing, allreduce_base.cc:751-815).

    With ``wire``, each chunk is encoded ONCE by its owner and the
    encoded bytes are forwarded VERBATIM hop to hop (the owner keeps
    the decode of its own encoding). Decoding is deterministic in the
    encoded bytes, so all p ranks end bit-identical — the rabit
    replay/recovery contract. (Re-encoding per hop looks lossless but
    drifts the int8 block scale by float ULPs each hop, and ranks at
    different hop distances then disagree at the last bit.)

    ``reverse`` gathers around the counter-rotating ring (pairs with
    ``ring_reduce_scatter(reverse=True)``); rank order is unchanged.

    ``groups`` gathers over every sub-ring concurrently: each rank ends
    with the concatenation of its OWN group's chunks in group order —
    the intra-host phase of :func:`hier_allreduce`."""
    p = axis_size(axis_name)
    if groups is None:
        size, idx = p, lax.axis_index(axis_name)
        perm = _ring_perm(p, reverse)
    else:
        size, local_of = _group_tables(groups, p)
        idx = jnp.asarray(local_of)[lax.axis_index(axis_name)]
        perm = _group_ring_perm(groups, reverse)
    if size == 1:
        return x
    wire = _normalize_wire(wire, SUM, x.dtype, x.shape[0])
    _, ag_codec, blk = _parse_wire(wire) if wire else (None, None, 0)
    if ag_codec is not None:
        enc = _codec_encode(x, ag_codec, blk)
        x = _codec_decode(enc, ag_codec, x.shape).astype(x.dtype)
    out = jnp.zeros((size,) + x.shape, x.dtype)
    out = lax.dynamic_update_index_in_dim(out, x, idx, 0)
    for step in range(size - 1):
        if reverse:
            send_i = (idx + step) % size
            recv_i = (idx + step + 1) % size
        else:
            send_i = (idx - step) % size
            recv_i = (idx - step - 1) % size
        if ag_codec is None:
            send = lax.dynamic_index_in_dim(out, send_i, 0,
                                            keepdims=False)
            got = lax.ppermute(send, axis_name, perm)
        else:
            # the chunk sent at step s is exactly the one received at
            # step s-1 (own chunk at s=0) in either direction: forward
            # its encoding verbatim
            enc = tuple(lax.ppermute(e, axis_name, perm) for e in enc)
            got = _codec_decode(enc, ag_codec, x.shape).astype(x.dtype)
        out = lax.dynamic_update_index_in_dim(out, got, recv_i, 0)
    return out.reshape((size * x.shape[0],) + x.shape[1:])


def _pad_to_multiple(x: jax.Array, p: int):
    n = x.shape[0]
    rem = (-n) % p
    if rem:
        x = jnp.concatenate([x, jnp.zeros((rem,) + x.shape[1:], x.dtype)])
    return x, n


def ring_allreduce(x: jax.Array, axis_name: str, op: int = SUM,
                   wire: str | None = None,
                   reverse: bool = False,
                   groups=None) -> jax.Array:
    """Ring allreduce = reduce-scatter + all-gather (TryAllreduceRing,
    allreduce_base.cc:930-949). Handles lengths not divisible by p by
    zero-padding (zero is the identity for sum/bitor; for max/min the
    padding elements are reduced but sliced off before return).

    ``wire`` ("bf16" | "int8", float SUM only) compresses only the
    ppermute'd bytes — EQuARX-style wire quantization with
    full-precision on-device accumulation. All ranks still end
    bit-identical (the all-gather rounds the owner's chunk through the
    same encoding the hops use).

    ``groups`` allreduces over every sub-ring concurrently (each rank's
    result reduces only its own group's contributions) — the inter-host
    phase of :func:`hier_allreduce` runs this over slot rings."""
    if x.ndim != 1:
        raise ValueError(
            f"ring_allreduce takes a 1-D per-shard array, got shape "
            f"{x.shape}; flatten first (the chunking math silently "
            "misreduces higher-rank inputs)")
    p = axis_size(axis_name)
    size = p if groups is None else _group_tables(groups, p)[0]
    if size == 1:
        return x
    wire = _normalize_wire(wire, op, x.dtype)  # eligibility; pad below
    # int8 wants the per-rank chunk to tile into scaling blocks;
    # zero-padding is the SUM identity and the tail is sliced off, so
    # pad up rather than silently degrading real-world sizes to bf16
    xp, n = _pad_to_multiple(x, _wire_pad_mult(wire, size))
    mine = ring_reduce_scatter(xp, axis_name, op, wire=wire,
                               reverse=reverse, groups=groups)
    full = ring_all_gather(mine, axis_name, wire=wire, reverse=reverse,
                           groups=groups)
    return full[:n]


def bidir_ring_allreduce(x: jax.Array, axis_name: str, op: int = SUM,
                         wire: str | None = None,
                         groups=None) -> jax.Array:
    """Bidirectional ring allreduce: the payload splits in half and the
    two halves run counter-rotating rings (forward and reverse ppermute
    schedules) that XLA overlaps — on a 1-D mesh whose links are
    full-duplex this doubles utilized link bandwidth, halving the
    per-step wire time of a single ring (each direction moves n/2p per
    hop instead of n/p).

    Same contract as :func:`ring_allreduce` (1-D per-shard input,
    ``wire`` on float SUM, ``groups`` sub-rings — both counter-rotating
    halves follow the same grouped order, so a skew-adaptive rotation
    applies to both directions). Payloads too small to split (< 2p
    elements) run a single forward ring — at that size the split only
    adds latency."""
    if x.ndim != 1:
        raise ValueError(
            f"bidir_ring_allreduce takes a 1-D per-shard array, got "
            f"shape {x.shape}; flatten first")
    p = axis_size(axis_name)
    n = x.shape[0]
    if p == 1:
        return x
    if n < 2 * p:
        return ring_allreduce(x, axis_name, op, wire=wire, groups=groups)
    half = n - n // 2
    lo = ring_allreduce(x[:half], axis_name, op, wire=wire, groups=groups)
    hi = ring_allreduce(x[half:], axis_name, op, wire=wire, reverse=True,
                        groups=groups)
    return jnp.concatenate([lo, hi])


@functools.lru_cache(maxsize=None)
def _swing_tables(p: int):
    """Static Swing schedule for a power-of-two world (arXiv:2401.09356).

    Peer of rank i at step s is ``(i ± rho(s)) mod p`` (+ for even
    ranks, − for odd) with ``rho(s) = (1-(-2)^(s+1))/3`` — the
    1,-1,3,-5,11,… distance sequence whose property is that any two
    ranks meet (directly or transitively) in log2(p) steps while
    consecutive steps land on maximally distant ring neighbors.

    Returns ``(peers, send_idx, recv_idx)``: ``peers[s]`` is the length-p
    partner table (an involution, asserted); ``send_idx[s]`` /
    ``recv_idx[s]`` are ``[p, 2^(k-1-s)]`` int arrays of the chunk
    indices rank i ships / keeps at reduce-scatter step s. They are
    built backward from the final ownership (rank i ends owning chunk i)
    via ``resp[s-1][i] = resp[s][i] ∪ resp[s][peer]``; the asserted
    invariants (peer sets disjoint, sizes exactly halving, step-0 union
    covering all p chunks) are what make the halving schedule a correct
    reduce-scatter. The all-gather runs the same tables in reverse."""
    if p < 2 or p & (p - 1):
        raise ValueError(f"swing needs a power-of-two world, got {p}")
    k = p.bit_length() - 1
    peers = []
    for s in range(k):
        d = (1 - (-2) ** (s + 1)) // 3
        row = [(i + d) % p if i % 2 == 0 else (i - d) % p
               for i in range(p)]
        assert all(row[row[i]] == i for i in range(p)), (p, s, row)
        peers.append(row)
    resp = [None] * k
    resp[k - 1] = [frozenset((i,)) for i in range(p)]
    for s in range(k - 1, 0, -1):
        resp[s - 1] = [resp[s][i] | resp[s][peers[s][i]] for i in range(p)]
    for s in range(k):
        for i in range(p):
            assert len(resp[s][i]) == 1 << (k - 1 - s), (p, s, i)
            assert not (resp[s][i] & resp[s][peers[s][i]]), (p, s, i)
    for i in range(p):
        assert len(resp[0][i] | resp[0][peers[0][i]]) == p, (p, i)
    send_idx = [np.array([sorted(resp[s][peers[s][i]]) for i in range(p)],
                         dtype=np.int32) for s in range(k)]
    recv_idx = [np.array([sorted(resp[s][i]) for i in range(p)],
                         dtype=np.int32) for s in range(k)]
    return peers, send_idx, recv_idx


def swing_allreduce(x: jax.Array, axis_name: str, op: int = SUM,
                    wire: str | None = None,
                    groups=None) -> jax.Array:
    """Swing allreduce (arXiv:2401.09356): recursive distance-halving
    reduce-scatter + the mirrored all-gather, 2·log2(p) steps total
    against the ring's 2(p-1) — the latency sweet spot between the tree
    and the ring for mid-size payloads. Power-of-two worlds only;
    other sizes fall back cleanly to :func:`ring_allreduce` (same
    result, different schedule).

    Same contract as :func:`ring_allreduce`: 1-D per-shard input;
    ``wire`` ("bf16" | "int8", float SUM only) compresses only the
    ppermute'd bytes, accumulation stays full-precision, and the
    all-gather forwards each chunk's encoding verbatim so all p ranks
    end bit-identical.

    ``groups`` runs the schedule over every sub-ring concurrently
    (power-of-two GROUP size required; otherwise the grouped ring
    fallback) — the inter-host phase of
    ``hier_allreduce(inter_method="swing")``."""
    if x.ndim != 1:
        raise ValueError(
            f"swing_allreduce takes a 1-D per-shard array, got shape "
            f"{x.shape}; flatten first")
    p = axis_size(axis_name)
    if groups is None:
        size, local_of = p, None
    else:
        size, local_of = _group_tables(groups, p)
    if size == 1:
        return x
    if size & (size - 1) or x.shape[0] == 0:
        return ring_allreduce(x, axis_name, op, wire=wire, groups=groups)
    wire = _normalize_wire(wire, op, x.dtype)  # eligibility; pad below
    rs_codec, ag_codec, blk = (_parse_wire(wire) if wire
                               else (None, None, 0))
    xp, n = _pad_to_multiple(x, _wire_pad_mult(wire, size))
    peers, send_idx, recv_idx = _swing_tables(size)
    k = len(peers)
    combine = jax_reduce_fn(op)
    idx = lax.axis_index(axis_name)
    if groups is not None:
        idx = jnp.asarray(local_of)[idx]

    def _peer_perm(s):
        # flat: rank i <-> peers[s][i]; grouped: the same involution
        # inside every sub-ring at once, in local coordinates
        if groups is None:
            return [(i, peers[s][i]) for i in range(p)]
        return [(grp[i], grp[peers[s][i]]) for grp in groups
                for i in range(size)]

    # EQuARX hop contract (see ring_reduce_scatter): quantized-wire
    # contributions decode to f32 and fold in f32; cast back at the end
    acc_dtype = jnp.float32 if rs_codec else xp.dtype
    chunks = xp.reshape(size, -1).astype(acc_dtype)
    m = chunks.shape[1]

    # Reduce-scatter: at step s exchange with peers[s], shipping the
    # accumulated chunks the peer is responsible for (send_idx[s]) and
    # folding the received contributions into ours (recv_idx[s]). The
    # peer ships its rows sorted by chunk index — the same order as our
    # recv_idx rows — so received rows align without a permutation.
    for s in range(k):
        perm = _peer_perm(s)
        send_rows = jnp.asarray(send_idx[s])[idx]
        recv_rows = jnp.asarray(recv_idx[s])[idx]
        send = jnp.take(chunks, send_rows, axis=0)
        if rs_codec is None:
            got = lax.ppermute(send, axis_name, perm)
        else:
            enc = _codec_encode(send, rs_codec, blk)
            enc = tuple(lax.ppermute(e, axis_name, perm) for e in enc)
            got = _codec_decode(enc, rs_codec, send.shape)
        cur = jnp.take(chunks, recv_rows, axis=0)
        chunks = chunks.at[recv_rows].set(combine(cur, got))
    mine = lax.dynamic_index_in_dim(chunks, idx, 0,
                                    keepdims=False).astype(xp.dtype)

    # All-gather: the same schedule backward — at step s each rank has
    # its responsibility set resp[s] complete and ships it, receiving
    # the peer's. With a wire, each chunk is encoded ONCE by its owner
    # and the encoded bytes travel verbatim thereafter (see
    # ring_all_gather on why re-encoding per hop breaks the
    # bit-identical-ranks replay contract).
    if ag_codec is None:
        out = jnp.zeros((size, m), mine.dtype)
        out = lax.dynamic_update_index_in_dim(out, mine, idx, 0)
        for s in range(k - 1, -1, -1):
            perm = _peer_perm(s)
            send_rows = jnp.asarray(recv_idx[s])[idx]
            recv_rows = jnp.asarray(send_idx[s])[idx]
            send = jnp.take(out, send_rows, axis=0)
            got = lax.ppermute(send, axis_name, perm)
            out = out.at[recv_rows].set(got)
    else:
        enc0 = _codec_encode(mine, ag_codec, blk)
        store = tuple(
            lax.dynamic_update_index_in_dim(
                jnp.zeros((size,) + e.shape, e.dtype), e, idx, 0)
            for e in enc0)
        for s in range(k - 1, -1, -1):
            perm = _peer_perm(s)
            send_rows = jnp.asarray(recv_idx[s])[idx]
            recv_rows = jnp.asarray(send_idx[s])[idx]
            got = tuple(
                lax.ppermute(jnp.take(e, send_rows, axis=0),
                             axis_name, perm) for e in store)
            store = tuple(e.at[recv_rows].set(g)
                          for e, g in zip(store, got))
        out = _codec_decode(store, ag_codec,
                            (size, m)).astype(mine.dtype)
    return out.reshape(size * m)[:n]


def _intra_axis_groups(groups):
    return [list(grp) for grp in groups]


def _intra_reduce_scatter(x: jax.Array, axis_name: str, op: int,
                          groups) -> jax.Array:
    """Intra-host reduce-scatter phase. The local links are the fast
    fabric (shared memory in-process, ICI on a slice), so SUM rides
    XLA's native grouped ReduceScatter HLO — measured ~3-4x the manual
    ppermute ring on the CPU backend — with ownership landing on the
    local index, the same layout as the grouped manual ring. Ops with
    no native scatter variant (MAX/MIN/BITOR) run the manual grouped
    ring instead."""
    if op == SUM:
        return lax.psum_scatter(
            x, axis_name, scatter_dimension=0, tiled=True,
            axis_index_groups=_intra_axis_groups(groups))
    return ring_reduce_scatter(x, axis_name, op, groups=groups)


def _intra_all_gather(x: jax.Array, axis_name: str, groups) -> jax.Array:
    """Intra-host all-gather phase via the native grouped AllGather HLO;
    the group-order concatenation matches ``ring_all_gather(groups=)``."""
    return lax.all_gather(
        x, axis_name, axis=0, tiled=True,
        axis_index_groups=_intra_axis_groups(groups))


def hier_allreduce(x: jax.Array, axis_name: str, op: int = SUM,
                   groups=None, wire: str | None = None,
                   inter_method: str = "ring") -> jax.Array:
    """Two-level hierarchical allreduce over host groups (ROADMAP open
    item 4), expressed as a composition of the grouped primitives:

    1. intra-host reduce-scatter over each group (cheap UDS/ICI
       links — XLA's native grouped collective where the op allows,
       :func:`_intra_reduce_scatter`; never wire-quantized —
       quantization buys nothing where bandwidth is free);
    2. inter-host allreduce of the reduced shards over the slot rings
       (rank j of every host forms ring j — the host-delegate fabric;
       this is the only phase crossing the slow links, so ``wire``
       applies here);
    3. intra-host ring all-gather redistributing the finished shards.

    With p ranks on H hosts (g = p/H per host), the slow links carry
    2n(H-1)/(gH) bytes per rank instead of the flat ring's 2n(p-1)/p —
    a ~g-fold reduction — in 2(g-1) + 2(H-1) ppermute steps instead of
    2(p-1).

    Degenerate worlds short-circuit instead of running empty phases:
    unknown topology (``groups=None``) and one-rank-per-host run the
    flat ``inter_method`` schedule (every link is inter-host); a single
    group runs one flat unquantized ring (every link is intra-host);
    ragged groups fall back to the flat schedule (SPMD needs uniform
    chunk shapes). All p ranks end bit-identical — each global chunk's
    bits are produced once, by its slot ring, and phase 3 only copies
    them (the replay/recovery contract; note hier and flat ring SUM
    results may differ from each other by float association)."""
    if x.ndim != 1:
        raise ValueError(
            f"hier_allreduce takes a 1-D per-shard array, got shape "
            f"{x.shape}; flatten first")
    if inter_method not in ("ring", "swing"):
        raise ValueError(
            f"inter_method must be 'ring' or 'swing', got {inter_method!r}")
    p = axis_size(axis_name)
    if p == 1:
        return x
    flat_fn = swing_allreduce if inter_method == "swing" else ring_allreduce
    if not groups or not _topology.is_hierarchical(groups, p):
        if groups and len(groups) == 1:
            # all ranks share one host: pure intra-host path, and local
            # links never pay for a lossy wire
            return ring_allreduce(x, axis_name, op, wire=None)
        # unknown topology, one rank per host, or ragged groups: the
        # flat schedule IS the inter-host path
        return flat_fn(x, axis_name, op, wire=wire)
    groups = tuple(tuple(int(r) for r in grp) for grp in groups)
    g, _ = _group_tables(groups, p)
    slots = _topology.slot_rings(groups)
    wire = _normalize_wire(wire, op, x.dtype)  # eligibility; pad below
    # pad so the intra shard (n/g) splits evenly into inter chunks
    # (n/p); the int8 block constraint lands on the inter phase's
    # per-rank chunk
    xp, n = _pad_to_multiple(x, _wire_pad_mult(wire, p))
    with telemetry.trace_annotation("rabit_hier_reduce_scatter"):
        mine = _intra_reduce_scatter(xp, axis_name, op, groups)
    with telemetry.trace_annotation("rabit_hier_inter"):
        if inter_method == "swing":
            mine = swing_allreduce(mine, axis_name, op, wire=wire,
                                   groups=slots)
        else:
            mine = ring_allreduce(mine, axis_name, op, wire=wire,
                                  groups=slots)
    with telemetry.trace_annotation("rabit_hier_allgather"):
        full = _intra_all_gather(mine, axis_name, groups)
    return full[:n]


def tree_allreduce(x: jax.Array, axis_name: str, op: int = SUM) -> jax.Array:
    """Latency-optimal allreduce — XLA's built-in reduction
    (TryAllreduceTree equivalent, allreduce_base.cc:475-640). BitOR has
    no lax primitive, so it all-gathers and reduces locally (log-depth
    on ICI; small buffers only — device_allreduce routes big BitOR
    through the ring path)."""
    if op == SUM:
        return lax.psum(x, axis_name)
    if op == MAX:
        return lax.pmax(x, axis_name)
    if op == MIN:
        return lax.pmin(x, axis_name)
    if op == BITOR:
        gathered = lax.all_gather(x, axis_name)  # [p, ...]
        return functools.reduce(
            jnp.bitwise_or, [gathered[i] for i in range(gathered.shape[0])])
    raise ValueError(f"unknown op {op}")


def preagg_allreduce(x: jax.Array, axis_name: str, op: int = SUM,
                     groups=None) -> jax.Array:
    """Pre-aggregating allreduce for a world with a known laggard — the
    arXiv:1804.05349 core idea rendered in static SPMD form.

    ``groups`` is ``((early...), (laggard,))``
    (``telemetry.skew.preagg_groups``): the measured arrival order is a
    static schedule input, not a runtime discovery — SPMD programs
    cannot change membership mid-flight, but they CAN order the
    dependency graph so nothing waits on the laggard until its
    contribution is genuinely needed.

    1. the arrived subgroup reduces among itself (grouped
       psum/pmax/pmin; the laggard sits in a singleton group and
       exchanges nothing — on an async fabric this phase completes
       while the laggard is still on its way);
    2. on arrival, one full-duplex ppermute exchange at the fold root
       — ``early[0]``, which ``adapt_plan`` places so that the elected
       earliest-arrival rank leads the early tuple
       (``skew.preagg_groups(root=...)``): the laggard's raw vector
       goes out, the subgroup result comes back;
    3. the laggard's vector binomially doubles to the remaining ranks
       and every rank folds locally.

    Total post-arrival work is one exchange plus ceil(log2(p-1))
    doubling hops of n bytes — against the full reduction a flat
    schedule would only START at arrival. The extra fold traffic is why
    dispatch gates this behind a measured per-MiB skew threshold
    (``rabit_skew_preagg_ms``). SUM/MAX/MIN only; the wire codec never
    applies (raw ppermute payloads). All ranks end bit-identical: each
    value is produced once and copied."""
    if x.ndim != 1:
        raise ValueError(
            f"preagg_allreduce takes a 1-D per-shard array, got shape "
            f"{x.shape}; flatten first")
    p = axis_size(axis_name)
    if p == 1:
        return x
    if (not groups or len(groups) != 2 or len(groups[1]) != 1
            or sorted(groups[0] + groups[1]) != list(range(p))):
        raise ValueError(
            f"preagg groups must be ((early...), (laggard,)) covering "
            f"ranks 0..{p - 1}, got {groups!r}")
    if op not in (SUM, MAX, MIN):
        raise ValueError(
            f"preagg_allreduce supports SUM/MAX/MIN, got op {op}")
    early, laggard = tuple(groups[0]), groups[1][0]
    root = early[0]
    grouped = {SUM: lax.psum, MAX: lax.pmax, MIN: lax.pmin}[op]
    combine = {SUM: jnp.add, MAX: jnp.maximum, MIN: jnp.minimum}[op]
    with telemetry.trace_annotation("rabit_preagg_allreduce"):
        # phase 1: subgroup reduction (the laggard's singleton group
        # reduces to its own contribution — no wire, no wait)
        partial = grouped(x, axis_name,
                          axis_index_groups=[list(early), [laggard]])
        idx = lax.axis_index(axis_name)
        # phase 2: full-duplex exchange at the fold root
        recv = lax.ppermute(partial, axis_name,
                            perm=[(laggard, root), (root, laggard)])
        sub = jnp.where(idx == laggard, recv, partial)
        lag_vec = jnp.where(
            (idx == laggard) | (idx == root),
            jnp.where(idx == root, recv, partial), jnp.zeros_like(x))
        # phase 3: binomial doubling of the laggard's vector from
        # {laggard, root} until every rank holds it, then a local fold
        holders = [laggard, root]
        others = [r for r in early[1:]]
        while others:
            pairs = list(zip(holders, others))
            sent = lax.ppermute(lag_vec, axis_name, perm=pairs)
            newly = [d for (_, d) in pairs]
            mask = functools.reduce(jnp.logical_or,
                                    [idx == d for d in newly])
            lag_vec = jnp.where(mask, sent, lag_vec)
            holders = holders + newly
            others = others[len(pairs):]
        return combine(sub, lag_vec)


def psum_identity_grad(x: jax.Array, axis_name: str) -> jax.Array:
    """``lax.psum`` whose backward pass is the identity — for
    ``check_vma=False`` (unchecked) shard_map contexts ONLY.

    For model-parallel partial-sum reductions (e.g. combining
    tensor-parallel matmul partials) the mathematically correct cotangent
    of each partial is the (replicated) cotangent of the sum. Under
    unchecked shard_map, ``lax.psum``'s transpose rule applies a
    *second* psum to the already-replicated cotangent, scaling upstream
    gradients by the axis size; this wrapper pins the correct identity
    backward. Under ``check_vma=True`` plain ``lax.psum`` is already
    gradient-correct (its transpose is a vma cast, and the automatic
    replicated->varying casts transpose to psum) — use it directly
    there; composing THIS op with the checker's automatic casts
    double-counts the other way.
    """
    @jax.custom_vjp
    def f(v):
        return lax.psum(v, axis_name)

    f.defvjp(lambda v: (lax.psum(v, axis_name), None),
             lambda _, g: (g,))
    return f(x)


def ident_psum_grad(x: jax.Array, axis_name: str) -> jax.Array:
    """Identity whose backward pass is ``lax.psum`` over ``axis_name`` —
    the conjugate of :func:`psum_identity_grad`, for unchecked shard_map
    contexts only (see that function's note on ``check_vma=True``).

    Place it where a replicated activation *enters* a model-parallel
    region (before einsums with axis-sharded weights): each shard's
    backward then contributes only its local paths, and this operator
    collects them into the full cotangent, so gradients of everything
    upstream come out complete and identical on every shard of the axis.
    (Megatron's f/g conjugate-operator pair: this is f, and
    ``psum_identity_grad`` — applied where partial results *leave* the
    region — is g.)
    """
    @jax.custom_vjp
    def f(v):
        return v

    f.defvjp(lambda v: (v, None),
             lambda _, g: (lax.psum(g, axis_name),))
    return f(x)


def bcast_from_root(x: jax.Array, axis_name: str, root: int = 0) -> jax.Array:
    """Broadcast rank ``root``'s value to all ranks (TryBroadcast,
    allreduce_base.cc:649-737): mask non-root contributions to the
    additive identity and psum — vma-correct under the replication
    checker (psum of a varying value is replicated). ``lax.pbroadcast``
    (the CollectiveBroadcast HLO) would be the direct lowering but its
    vma inference is not wired in this jax ("unbound axis name" under
    shard_map); XLA pattern-matches select+allreduce anyway."""
    idx = lax.axis_index(axis_name)
    contrib = jnp.where(idx == root, x, jnp.zeros_like(x))
    if x.dtype == jnp.bool_:
        return lax.psum(contrib.astype(jnp.int32), axis_name).astype(x.dtype)
    return lax.psum(contrib, axis_name)


# ---------------------------------------------------------------------------
# Host-level conveniences: operate on a global array whose leading axis is
# sharded across a mesh axis (one slice per device = one "rank").
# ---------------------------------------------------------------------------

# method name -> per-shard allreduce over a flat 1-D buffer
_METHOD_FNS = {
    "ring": ring_allreduce,
    "bidir": bidir_ring_allreduce,
    "swing": swing_allreduce,
}


def _per_shard_allreduce(flat, axis: str, op: int, method: str,
                         wire: str | None, groups=None):
    # named_scope (metadata-only, zero jaxpr equations either way) makes
    # the chosen schedule attributable in XLA profiles when telemetry is
    # on; nullcontext when off
    # spec separators (:@) are not valid named_scope characters
    wtag = wire.replace(":", "_").replace("@", "_") if wire else ""
    label = f"rabit_allreduce_{method}" + (f"_{wtag}" if wtag else "")
    with telemetry.trace_annotation(label):
        if method == "tree":
            return tree_allreduce(flat, axis, op)
        if method == "hier":
            return hier_allreduce(flat, axis, op, groups=groups, wire=wire)
        if method == "preagg":
            return preagg_allreduce(flat, axis, op, groups=groups)
        return _METHOD_FNS[method](flat, axis, op, wire=wire, groups=groups)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "op", "method",
                                             "wire", "groups"))
def _allreduce_global(xs, mesh: Mesh, axis: str, op: int, method: str,
                      wire: str | None = None, groups=None):
    def per_shard(x):
        x = x.reshape(x.shape[1:])  # drop the per-device leading 1
        flat = x.reshape(-1)
        return _per_shard_allreduce(flat, axis, op, method, wire,
                                    groups).reshape(x.shape)
    # ring-family bodies are ppermute chains — and the BitOR tree body
    # is an all_gather + local fold — whose replicated outputs the
    # static checker cannot infer; the psum/pmax/pmin tree path is
    # fully checked
    sm = (shard_map if method == "tree" and op != BITOR
          else unchecked_shard_map)
    f = sm(per_shard, mesh=mesh, in_specs=P(axis), out_specs=P())
    return f(xs)


def _skew_sync_point(mesh: Mesh, axis: str) -> None:
    """Fleet agreement boundary for skew adaptation.

    Adapted methods/groups are STATIC jit arguments: in a
    multi-controller SPMD world every process must derive them from the
    same digest, or processes trace different programs for the same
    collective round and deadlock. This helper fires at deterministic
    dispatch counts (``skew.sync_due`` — program order is the
    rendezvous, identical on every process) and, in multi-process
    worlds, broadcasts process 0's candidate digest over the device
    fabric as a fixed-shape float vector whose program is
    digest-independent. Every process adopts the broadcast result —
    :meth:`SkewMonitor.applied` — and ONLY that digest ever reaches
    ``adapt_plan`` or dispatch, so schedules switch in lockstep at
    agreed boundaries (the digest's tracker-side epoch says which
    election is in force). Single-process worlds adopt the local
    candidate directly; multi-axis multi-process meshes (no engine
    builds one) conservatively adopt None — adaptation stays off
    rather than risking a divergent broadcast layout."""
    if not _skew.sync_due():
        return
    mon = _skew.monitor()
    if jax.process_count() == 1:
        mon.set_applied(mon.current())
        return
    if len(mesh.axis_names) != 1:
        mon.set_applied(None)
        return
    world = mesh.shape[axis]
    vec = np.asarray(_skew.encode_digest(mon.current(), world), np.float32)
    shards = [jax.device_put(vec.reshape(1, -1), d)
              for d in mesh.local_devices]
    xs = jax.make_array_from_single_device_arrays(
        (world, vec.size), NamedSharding(mesh, P(axis)),
        shards)
    out = _broadcast_global(xs, mesh, axis, 0)
    agreed = _skew.decode_digest(
        np.asarray(out.addressable_data(0)).reshape(-1))
    mon.set_applied(agreed)
    telemetry.count("dispatch.skew_sync")


def _rotation_for(mesh: Mesh, axis: str, world: int):
    """Skew adaptation for the RS/AG primitives: ``(order, adapted)``.

    Rotation is the ONLY plan these schedules admit (there is no tree
    to re-root and nothing to pre-aggregate — the payload is already
    the substrate), so this reads the fleet-AGREED digest directly
    instead of going through :func:`skew.adapt_plan`'s method-switching
    logic: laggard named and inside this world -> walk the ring in
    laggard-last order; anything else -> ``(None, None)``, which keeps
    the traced program byte-identical to the unadapted one."""
    if not _skew.adapt_enabled() or world < 2:
        return None, None
    _skew_sync_point(mesh, axis)
    lag = _skew.laggard_of(_skew.monitor().applied())
    if lag is None or not 0 <= lag < world:
        _skew.note_applied(None)
        return None, None
    adapted = f"rotate@{lag}"
    _skew.note_applied(adapted)
    telemetry.count("dispatch.skew_adapted")
    return _skew.rotation_order(world, lag), adapted


def _stamp_exposed(sp, t0: float) -> None:
    """Split a live span's wall time into exposed vs overlapped wire
    attrs. A synchronous collective blocks the host for its whole
    duration, so everything from dispatch to block_until_ready is
    exposed and nothing is overlapped; the async handles stamp the
    measured split instead. Cross-rank critical-path stitching
    (telemetry/crossrank.py) prefers these attrs over the raw span
    duration, so the tables stay honest when sync and async rounds
    mix."""
    sp.attrs["wire_exposed_ms"] = (time.perf_counter() - t0) * 1e3
    sp.attrs["wire_overlapped_ms"] = 0.0


def device_allreduce(xs: jax.Array, mesh: Mesh, op: int = SUM,
                     axis: Optional[str] = None,
                     method: str = "auto",
                     wire: Optional[str] = "auto",
                     groups=None) -> jax.Array:
    """Allreduce across a mesh axis. ``xs`` has shape [p, ...] with the
    leading axis sharded over ``axis``; returns the elementwise reduction
    with shape ``xs.shape[1:]``, replicated.

    ``method="auto"`` picks among {tree, ring, bidir, swing} per payload
    size from the committed ``COLLECTIVE_SWEEP_*`` dispatch table
    (``parallel/dispatch.py``); without a table it reproduces the
    reference's documented-but-never-wired crossover
    (reduce_ring_mincount=32768, SURVEY §2 #3): tree below 32k elements,
    ring above, plus the big-BitOR ring override.

    ``wire``: EQuARX-style wire quantization on the ring-family paths
    (float SUM payloads only; the tree path ignores it). "bf16"/"int8"
    force it on for this call; None/"none" force it off; the default
    "auto" engages a config/env-requested wire
    (``rabit_dataplane_wire``) only at payload sizes where measurement
    says it pays (the table's wire column, else
    ``rabit_dataplane_wire_mincount``).

    ``groups``: host grouping for the hierarchical schedule — explicit
    tuple-of-tuples, else resolved from the ``rabit_hier_group``
    override / tracker-discovered ``RABIT_HIER_GROUP`` env
    (``parallel/topology.py``). ``method="auto"`` picks ``hier`` when
    the table says hierarchy wins at this size AND the grouping is
    genuinely two-level; ``method="hier"`` on a degenerate world runs
    the matching flat schedule.

    The host call is the phase ``rabit.allreduce`` (``telemetry.phase``),
    with the children ``rabit.allreduce.resolve`` (skew, topology,
    dispatch) and ``rabit.allreduce.enqueue`` (the jitted program's
    call); what is left is its bookkeeping.
    """
    if axis is None:
        axis = mesh.axis_names[0]
    n = int(np.prod(xs.shape[1:]))
    with telemetry.phase("rabit.allreduce",
                         nbytes=n * xs.dtype.itemsize) as top:
        with telemetry.phase("rabit.allreduce.resolve", parent=top):
            method, wire, groups, adapted = _allreduce_plan(
                xs, mesh, axis, n, op, method, wire, groups)
        cost = _profile.record_cost(
            "allreduce", method, wire, n, xs.dtype.itemsize,
            mesh.shape[axis], group_size=len(groups[0]) if groups else None)
        extra = ({"cost_flops": cost["flops"],
                  "cost_wire_bytes": cost["wire_bytes"],
                  "cost_hops": cost["hops"]} if cost else {})
        if method == "hier" and groups:
            extra["hosts"] = len(groups)
        if adapted:
            extra["adapted"] = adapted
        sp = telemetry.span("allreduce", nbytes=n * xs.dtype.itemsize,
                            op=OP_NAMES.get(op, str(op)), method=method,
                            wire=wire, **extra)
        with sp:
            t0 = time.perf_counter()
            with telemetry.phase("rabit.allreduce.enqueue", parent=top), \
                    _profile.jit_probe("allreduce", _allreduce_global):
                out = _allreduce_global(xs, mesh, axis, op, method, wire,
                                        groups)
            if sp.live:
                # only when measuring: a span closed on dispatch would
                # time the async enqueue, not the collective
                out.block_until_ready()
                _stamp_exposed(sp, t0)
    return out


def _allreduce_plan(xs, mesh: Mesh, axis: str, n: int, op: int,
                    method: str, wire, groups):
    """``device_allreduce``'s schedule, ``(method, wire, groups,
    adapted)``: skew sync, topology, dispatch and the skew plan."""
    if _skew.adapt_enabled():
        # BEFORE resolve: dispatch's method-family election reads the
        # agreed digest too, so it must be current at this boundary
        _skew_sync_point(mesh, axis)
    groups = _topology.resolve_groups(mesh.shape[axis], explicit=groups)
    method, wire = _dispatch_resolve(n, xs.dtype, op, mesh.shape[axis],
                                     method=method, wire=wire,
                                     groups=groups)
    if method not in ("hier", "preagg"):
        groups = None  # flat schedules ignore topology: keep the jit
        #                cache key stable across grouping changes
    adapted = None
    if _skew.adapt_enabled():
        # skew adaptation only permutes the schedule (rotation groups /
        # preagg fold order are static jit args); arithmetic per rank
        # pair is unchanged, so the replay contract holds. Only the
        # fleet-AGREED digest may steer it: a per-process candidate is
        # a divergent static jit arg in a multi-controller world.
        plan = _skew.adapt_plan(method, mesh.shape[axis],
                                n * xs.dtype.itemsize,
                                OP_NAMES.get(op, str(op)), groups=groups,
                                digest=_skew.monitor().applied())
        if plan is not None:
            method, groups = plan["method"], plan["groups"]
            if method == "preagg":
                wire = None  # raw ppermute payloads, codec never applies
            adapted = f"{plan['kind']}@{plan['laggard']}"
        _skew.note_applied(adapted)
    return method, wire, groups, adapted


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "op", "wire",
                                             "order"))
def _reduce_scatter_global(xs, mesh: Mesh, axis: str, op: int,
                           wire: str | None = None, order=None):
    def per_shard(x):
        flat = x.reshape(-1)  # drop the per-device leading 1
        with telemetry.trace_annotation("rabit_reduce_scatter"):
            if order is None:
                return ring_reduce_scatter(flat, axis, op, wire=wire)
            # laggard-last rotation: walk the ring in ``order`` (a
            # static permutation of the axis) so the laggard owns the
            # final position of every chunk walk. Grouped RS lands
            # ownership on the LOCAL ring index, so pre-permuting the
            # input chunks by the same order keeps the contract that
            # rank i ends owning chunk i of the ORIGINAL layout.
            chunks = flat.reshape(len(order), -1)
            rot = jnp.concatenate([chunks[r] for r in order])
            return ring_reduce_scatter(rot, axis, op, wire=wire,
                                       groups=(order,))
    return unchecked_shard_map(per_shard, mesh=mesh, in_specs=P(axis),
                               out_specs=P(axis))(xs)


def device_reduce_scatter(xs: jax.Array, mesh: Mesh, op: int = SUM,
                          axis: Optional[str] = None,
                          wire: Optional[str] = None) -> jax.Array:
    """Reduce-scatter across a mesh axis, as a first-class collective
    (arXiv:2112.01075 makes the case that RS/AG are the substrate
    redistribution composes from). ``xs`` has shape [p, ...] with the
    leading axis sharded over ``axis``; returns a length-n 1-D array
    (n = prod(xs.shape[1:])) sharded over ``axis`` whose i-th shard —
    n/p elements starting at i*n/p — is chunk i of the elementwise
    reduction: rank i owns chunk i, the reference's ownership
    convention (allreduce_base.cc:829-918) and the layout
    :func:`device_allgather` inverts.

    n must divide by p: a composable primitive must not pad silently,
    the caller owns the chunk layout (:func:`device_allreduce` is the
    pad-and-slice convenience). ``wire`` compresses the shipped bytes
    as in :func:`ring_reduce_scatter` (float SUM only; the spec's RS
    phase codec applies); ``wire="auto"`` consults dispatch — the
    env-requested wire engages only where gating/adaptive election says
    it pays, exactly as in :func:`device_allreduce`."""
    if axis is None:
        axis = mesh.axis_names[0]
    p = mesh.shape[axis]
    n = int(np.prod(xs.shape[1:]))
    if n % p:
        raise ValueError(
            f"reduce_scatter payload of {n} elements must divide by the "
            f"axis size {p} (rank i owns chunk i of length n/p); pad the "
            "input or use device_allreduce")
    if wire == "auto":
        _, wire = _dispatch_resolve(n, xs.dtype, op, p, method="ring",
                                    wire="auto")
    wire = _normalize_wire(_canonical_wire(wire), op, xs.dtype, n // p)
    order, adapted = _rotation_for(mesh, axis, p)
    cost = _profile.record_cost("reduce_scatter", "ring", wire, n,
                                xs.dtype.itemsize, p, phase="rs")
    extra = ({"cost_flops": cost["flops"],
              "cost_wire_bytes": cost["wire_bytes"],
              "cost_hops": cost["hops"]} if cost else {})
    if adapted:
        extra["adapted"] = adapted
    sp = telemetry.span("reduce_scatter", nbytes=n * xs.dtype.itemsize,
                        op=OP_NAMES.get(op, str(op)), method="ring",
                        wire=wire, **extra)
    with sp:
        t0 = time.perf_counter()
        with _profile.jit_probe("reduce_scatter", _reduce_scatter_global):
            out = _reduce_scatter_global(xs, mesh, axis, op, wire, order)
        if sp.live:
            out.block_until_ready()
            _stamp_exposed(sp, t0)
    return out


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "wire",
                                             "order"))
def _allgather_global(xs, mesh: Mesh, axis: str, wire: str | None = None,
                      order=None):
    def per_shard(x):
        flat = x.reshape(-1)  # drop the per-device leading 1
        with telemetry.trace_annotation("rabit_allgather"):
            if order is None:
                return ring_all_gather(flat, axis, wire=wire)
            # laggard-last rotation: gather around the reordered ring
            # (the laggard's chunk enters last), then restore the
            # rank-order concatenation the contract promises — grouped
            # AG concatenates in GROUP order, so the inverse
            # permutation puts chunk of rank order[j] back at slot
            # order[j].
            gathered = ring_all_gather(flat, axis, wire=wire,
                                       groups=(order,))
            chunks = gathered.reshape(len(order), -1)
            inv = [0] * len(order)
            for j, r in enumerate(order):
                inv[r] = j
            return jnp.concatenate([chunks[inv[i]]
                                    for i in range(len(order))])
    return unchecked_shard_map(per_shard, mesh=mesh, in_specs=P(axis),
                               out_specs=P())(xs)


def device_allgather(xs: jax.Array, mesh: Mesh,
                     axis: Optional[str] = None,
                     wire: Optional[str] = None) -> jax.Array:
    """All-gather across a mesh axis, as a first-class collective: rank
    i contributes its slice ``xs[i]`` (m elements, flattened) and every
    rank ends with the length p*m rank-order concatenation, replicated
    (TryAllgatherRing, allreduce_base.cc:751-815). The inverse of
    :func:`device_reduce_scatter`'s ownership layout; hierarchical
    allreduce is literally RS + inter-host reduction + this.

    ``wire`` compresses the forwarded bytes as in
    :func:`ring_all_gather` (the spec's AG phase codec; float payloads,
    lossy, all ranks still bit-identical); ``wire="auto"`` consults
    dispatch's gate/adaptive election like the other entry points."""
    if axis is None:
        axis = mesh.axis_names[0]
    p = mesh.shape[axis]
    m = int(np.prod(xs.shape[1:]))
    n = p * m
    if wire == "auto":
        _, wire = _dispatch_resolve(n, xs.dtype, SUM, p, method="ring",
                                    wire="auto")
    wire = _normalize_wire(_canonical_wire(wire), SUM, xs.dtype, m)
    order, adapted = _rotation_for(mesh, axis, p)
    cost = _profile.record_cost("allgather", "ring", wire, n,
                                xs.dtype.itemsize, p, phase="ag")
    extra = ({"cost_flops": cost["flops"],
              "cost_wire_bytes": cost["wire_bytes"],
              "cost_hops": cost["hops"]} if cost else {})
    if adapted:
        extra["adapted"] = adapted
    sp = telemetry.span("allgather", nbytes=n * xs.dtype.itemsize,
                        method="ring", wire=wire, **extra)
    with sp:
        t0 = time.perf_counter()
        with _profile.jit_probe("allgather", _allgather_global):
            out = _allgather_global(xs, mesh, axis, wire, order)
        if sp.live:
            out.block_until_ready()
            _stamp_exposed(sp, t0)
    return out


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "op", "groups",
                                             "mult"))
def _hier_rs_global(xs, mesh: Mesh, axis: str, op: int, groups, mult: int):
    def per_shard(x):
        flat = x.reshape(-1)
        xp, _ = _pad_to_multiple(flat, mult)
        with telemetry.trace_annotation("rabit_hier_reduce_scatter"):
            return _intra_reduce_scatter(xp, axis, op, groups)[None, :]
    return unchecked_shard_map(per_shard, mesh=mesh, in_specs=P(axis),
                               out_specs=P(axis))(xs)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "op", "slots",
                                             "wire", "inter_method"))
def _hier_inter_global(xs, mesh: Mesh, axis: str, op: int, slots,
                       wire: str | None, inter_method: str):
    fn = swing_allreduce if inter_method == "swing" else ring_allreduce
    def per_shard(x):
        flat = x.reshape(-1)
        with telemetry.trace_annotation("rabit_hier_inter"):
            return fn(flat, axis, op, wire=wire, groups=slots)[None, :]
    return unchecked_shard_map(per_shard, mesh=mesh, in_specs=P(axis),
                               out_specs=P(axis))(xs)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "groups"))
def _hier_ag_global(xs, mesh: Mesh, axis: str, groups):
    def per_shard(x):
        flat = x.reshape(-1)
        with telemetry.trace_annotation("rabit_hier_allgather"):
            return _intra_all_gather(flat, axis, groups)
    return unchecked_shard_map(per_shard, mesh=mesh, in_specs=P(axis),
                               out_specs=P())(xs)


def device_hier_allreduce(xs: jax.Array, mesh: Mesh, op: int = SUM,
                          axis: Optional[str] = None,
                          groups=None, wire: Optional[str] = None,
                          inter_method: str = "ring",
                          phase_guard=None) -> jax.Array:
    """Phase-decomposed hierarchical allreduce: the same math as
    ``device_allreduce(method="hier")`` but dispatched as THREE device
    programs so the host observes the phase boundaries — each phase
    gets its own telemetry span (shared ``round`` id, ``phase`` attr,
    so cross-rank stitching attributes stragglers to a phase) and, via
    ``phase_guard``, its own watchdog deadline. The engines run this
    variant for ``rabit_reduce_method=hier``; the fused single-program
    path stays the ``device_allreduce`` fast path.

    ``phase_guard(phase_name, nbytes)`` must return a context manager
    (the engines pass a watchdog-guard factory scaled by
    ``rabit_hier_phase_deadline_scale``; default no-op). Degenerate
    topologies short-circuit to one flat program, same rules as
    :func:`hier_allreduce`."""
    if axis is None:
        axis = mesh.axis_names[0]
    p = mesh.shape[axis]
    groups = _topology.resolve_groups(p, explicit=groups)
    if not _topology.is_hierarchical(groups, p):
        if groups and len(groups) == 1:
            wire = None  # single host: every link is local
        flat = "swing" if inter_method == "swing" else "ring"
        return device_allreduce(xs, mesh, op=op, axis=axis, method=flat,
                                wire=wire or "none")
    adapted = None
    if _skew.adapt_enabled():
        # demote a lagging delegate to the tail of its host group: slot 0
        # (the inter-host delegate ring) moves to the earliest co-hosted
        # rank, the laggard only participates intra-host. Same agreement
        # contract as device_allreduce: sync first, act only on the
        # fleet-agreed digest.
        _skew_sync_point(mesh, axis)
        plan = _skew.adapt_plan("hier", p, int(np.prod(xs.shape[1:]))
                                * xs.dtype.itemsize,
                                OP_NAMES.get(op, str(op)), groups=groups,
                                digest=_skew.monitor().applied())
        if plan is not None:
            groups = plan["groups"]
            adapted = f"{plan['kind']}@{plan['laggard']}"
        _skew.note_applied(adapted)
    g, hosts = len(groups[0]), len(groups)
    slots = _topology.slot_rings(groups)
    shape = xs.shape[1:]
    n = int(np.prod(shape))
    itemsize = xs.dtype.itemsize
    if wire == "auto":
        _, wire = _dispatch_resolve(n // g, xs.dtype, op, hosts,
                                    method="ring", wire="auto")
    wire = _normalize_wire(_canonical_wire(wire), op, xs.dtype)
    mult = _wire_pad_mult(wire, p)
    n_pad = n + (-n) % mult
    rnd = telemetry.collective_round("hier_allreduce")
    opname = OP_NAMES.get(op, str(op))
    guard = phase_guard or (lambda name, nbytes: contextlib.nullcontext())

    def _phase(name, phase, nbytes, method, w, cost_n, cost_axis,
               cost_phase, fn, *args):
        cost = _profile.record_cost(name, method, w, cost_n, itemsize,
                                    cost_axis, phase=cost_phase,
                                    group_size=g)
        extra = ({"cost_flops": cost["flops"],
                  "cost_wire_bytes": cost["wire_bytes"],
                  "cost_hops": cost["hops"]} if cost else {})
        if adapted:
            extra["adapted"] = adapted
        sp = telemetry.span(name, nbytes=nbytes, op=opname, method=method,
                            wire=w, round=rnd, phase=phase, hosts=hosts,
                            group_size=g, **extra)
        with guard(name, nbytes):
            with sp:
                t0 = time.perf_counter()
                with _profile.jit_probe(name, fn):
                    out = fn(*args)
                if sp.live:
                    out.block_until_ready()
                    _stamp_exposed(sp, t0)
        return out

    mid = _phase("hier.reduce_scatter", "reduce_scatter",
                 n * itemsize, "ring", None, n, g, "rs",
                 _hier_rs_global, xs, mesh, axis, op, groups, mult)
    mid = _phase("hier.inter", "inter",
                 (n_pad // g) * itemsize, inter_method, wire,
                 n_pad // g, hosts, None,
                 _hier_inter_global, mid, mesh, axis, op, slots, wire,
                 inter_method)
    out = _phase("hier.allgather", "allgather",
                 n * itemsize, "ring", None, n_pad, g, "ag",
                 _hier_ag_global, mid, mesh, axis, groups)
    return out[:n].reshape(shape)


def bucket_allreduce(tree, axis_name: str, op: int = SUM,
                     wire: str | None = None, method: str = "ring",
                     presum_axis: Optional[str] = None):
    """DDP-style bucketed allreduce of a pytree, per-shard: leaves are
    flattened and concatenated into ONE contiguous buffer per dtype,
    each bucket runs a single collective, and the results are split back
    into the original structure. A training step over an l-leaf
    parameter tree thus issues one ring-family dispatch per dtype
    instead of l small ones — the per-collective latency the reference
    pays per tree node, paid once.

    ``presum_axis`` first psums every leaf over that (model-parallel)
    axis — the transformer's partial-gradient fold — before bucketing
    over ``axis_name``. ``method`` is a concrete per-shard schedule
    ("tree" | "ring" | "bidir" | "swing"; no "auto" here — per-shard
    code has no host table access; use :func:`device_allreduce_tree`
    for dispatched bucketing)."""
    if method != "tree" and method not in _METHOD_FNS:
        raise ValueError(
            f"method must be tree|ring|bidir|swing, got {method!r}")
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if presum_axis is not None:
        leaves = [lax.psum(leaf, presum_axis) for leaf in leaves]
    buckets: dict = {}
    for i, leaf in enumerate(leaves):
        buckets.setdefault(jnp.dtype(leaf.dtype), []).append(i)
    out = [None] * len(leaves)
    for idxs in buckets.values():
        flat = jnp.concatenate([leaves[i].reshape(-1) for i in idxs])
        red = _per_shard_allreduce(flat, axis_name, op, method, wire)
        off = 0
        for i in idxs:
            size = leaves[i].size
            out[i] = red[off:off + size].reshape(leaves[i].shape)
            off += size
    return jax.tree_util.tree_unflatten(treedef, out)


@functools.partial(jax.jit, static_argnames=("treedef", "mesh", "axis",
                                             "op", "spec"))
def _allreduce_tree_global(leaves, treedef, mesh: Mesh, axis: str, op: int,
                           spec):
    plan = {name: (mth, w or None) for name, mth, w in spec}

    def per_shard(shards):
        shards = [x.reshape(x.shape[1:]) for x in shards]
        buckets: dict = {}
        for i, x in enumerate(shards):
            buckets.setdefault(jnp.dtype(x.dtype), []).append(i)
        out = [None] * len(shards)
        for dt, idxs in buckets.items():
            mth, w = plan[dt.name]
            flat = jnp.concatenate([shards[i].reshape(-1) for i in idxs])
            red = _per_shard_allreduce(flat, axis, op, mth, w)
            off = 0
            for i in idxs:
                size = shards[i].size
                out[i] = red[off:off + size].reshape(shards[i].shape)
                off += size
        return tuple(out)

    methods = {mth for _, mth, _ in spec}
    sm = (shard_map if methods == {"tree"} and op != BITOR
          else unchecked_shard_map)
    f = sm(per_shard, mesh=mesh, in_specs=P(axis), out_specs=P())
    return jax.tree_util.tree_unflatten(treedef, f(tuple(leaves)))


def device_allreduce_tree(tree, mesh: Mesh, op: int = SUM,
                          axis: Optional[str] = None,
                          method: str = "auto",
                          wire: Optional[str] = "auto"):
    """Bucketed host-level allreduce of a pytree: every leaf has shape
    [p, ...] with the leading axis sharded over ``axis`` (the
    :func:`device_allreduce` layout); returns the same structure with
    each leaf reduced to ``leaf.shape[1:]``, replicated.

    Leaves are bucketed into one contiguous buffer per dtype and each
    bucket issues ONE collective, with ``method``/``wire`` resolved per
    bucket from the dispatch table on the bucket's TOTAL element count —
    so a tree of many small parameters reaches the bandwidth-optimal
    ring-family regime a per-leaf dispatch never sees."""
    if axis is None:
        axis = mesh.axis_names[0]
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    totals: dict = {}
    for leaf in leaves:
        dt = jnp.dtype(leaf.dtype)
        totals[dt] = totals.get(dt, 0) + int(np.prod(leaf.shape[1:]))
    spec = []
    nbytes = 0
    for dt, n in totals.items():
        mth, w = _dispatch_resolve(n, dt, op, mesh.shape[axis],
                                   method=method, wire=wire)
        spec.append((dt.name, mth, w or ""))  # "" keeps the key hashable
        nbytes += n * dt.itemsize
        _profile.record_cost("allreduce_tree", mth, w, n, dt.itemsize,
                             mesh.shape[axis])
    spec = tuple(sorted(spec))
    sp = telemetry.span(
        "allreduce_tree", nbytes=nbytes, op=OP_NAMES.get(op, str(op)),
        method=",".join(sorted({m for _, m, _ in spec})),
        buckets=len(spec), leaves=len(leaves))
    with sp:
        t0 = time.perf_counter()
        with _profile.jit_probe("allreduce_tree", _allreduce_tree_global):
            out = _allreduce_tree_global(tuple(leaves), treedef, mesh,
                                         axis, op, spec)
        if sp.live:
            jax.block_until_ready(out)
            _stamp_exposed(sp, t0)
    return out


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "root"))
def _broadcast_global(xs, mesh: Mesh, axis: str, root: int):
    def per_shard(x):
        x = x.reshape(x.shape[1:])
        with telemetry.trace_annotation("rabit_broadcast"):
            return bcast_from_root(x, axis, root)
    return shard_map(per_shard, mesh=mesh, in_specs=P(axis), out_specs=P())(xs)


def device_broadcast(xs: jax.Array, mesh: Mesh, root: int = 0,
                     axis: Optional[str] = None) -> jax.Array:
    """Broadcast the root slice of [p, ...] to all ranks; returns
    shape ``xs.shape[1:]`` replicated."""
    if axis is None:
        axis = mesh.axis_names[0]
    n = int(np.prod(xs.shape[1:]))
    _profile.record_cost("broadcast", "psum_mask", None, n,
                         xs.dtype.itemsize, mesh.shape[axis])
    sp = telemetry.span("broadcast", nbytes=n * xs.dtype.itemsize,
                        method="psum_mask", root=root)
    with sp:
        t0 = time.perf_counter()
        with _profile.jit_probe("broadcast", _broadcast_global):
            out = _broadcast_global(xs, mesh, axis, root)
        if sp.live:
            out.block_until_ready()
            _stamp_exposed(sp, t0)
    return out


def shard_over(mesh: Mesh, xs: np.ndarray, axis: Optional[str] = None):
    """Place a host array [p, ...] so its leading dim is sharded across
    the mesh axis — the 'each rank contributes a slice' layout used by
    the engine and tests."""
    if axis is None:
        axis = mesh.axis_names[0]
    sharding = NamedSharding(mesh, P(axis))
    return jax.device_put(xs, sharding)


# ---------------------------------------------------------------------------
# Async collectives: issue -> overlap -> wait (ROADMAP open item 3).
#
# jax arrays are futures — dispatching a jitted collective returns
# immediately and the wire work proceeds while the host (or the next
# device program, via data dependence) keeps computing. These entry
# points expose that as an explicit handle so callers can pipeline:
# bucket i's allreduce rides the wire while bucket i+1's backward is
# still computing. Off by default (``rabit_async_collectives``); the
# sync entry points above are byte-for-byte untouched when unset.
# ---------------------------------------------------------------------------

_ASYNC_ENV = "RABIT_ASYNC_COLLECTIVES"
_ASYNC_INFLIGHT_ENV = "RABIT_ASYNC_MAX_INFLIGHT"
ASYNC_MAX_INFLIGHT_DEFAULT = 4


def async_enabled() -> bool:
    """Master knob for the async collective pipelines (models, engine).
    The ``*_async`` entry points themselves work regardless — this
    gates the places that would silently change an existing sync
    code path's schedule."""
    return os.environ.get(_ASYNC_ENV, "").lower() in ("1", "true", "yes",
                                                      "on")


def async_max_inflight() -> int:
    """Cap on concurrently in-flight async collectives. Admitting one
    past the cap blocks on the OLDEST handle first — bounded device
    memory for staged payloads, and a natural back-pressure that keeps
    issue order == completion order."""
    try:
        return max(1, int(os.environ.get(_ASYNC_INFLIGHT_ENV,
                                         ASYNC_MAX_INFLIGHT_DEFAULT)))
    except ValueError:
        return ASYNC_MAX_INFLIGHT_DEFAULT


def configure_async(cfg: dict) -> None:
    """Export the async knobs from an engine config dict to the env,
    so model code (which never sees the config) reads one source of
    truth. Called by engine init; host env settings win only when the
    config is silent."""
    v = cfg.get("rabit_async_collectives")
    if v is not None:
        os.environ[_ASYNC_ENV] = str(v)
    v = cfg.get("rabit_async_max_inflight")
    if v is not None:
        os.environ[_ASYNC_INFLIGHT_ENV] = str(v)


_INFLIGHT_LOCK = threading.Lock()
# weakrefs: the window must never keep a dropped handle alive — its
# __del__ IS the drop-detection path (warn + counter + guard disarm)
_INFLIGHT: list = []


def _admit(handle) -> None:
    # Never wait while holding the lock: wait() retires, which locks.
    while True:
        with _INFLIGHT_LOCK:
            _INFLIGHT[:] = [r for r in _INFLIGHT if r() is not None]
            if len(_INFLIGHT) < async_max_inflight():
                _INFLIGHT.append(weakref.ref(handle))
                return
            oldest = _INFLIGHT[0]()
        if oldest is None:
            continue  # died between prune and deref; re-prune
        oldest.wait()


def _retire(handle) -> None:
    with _INFLIGHT_LOCK:
        _INFLIGHT[:] = [r for r in _INFLIGHT
                        if r() is not None and r() is not handle]


def inflight_count() -> int:
    with _INFLIGHT_LOCK:
        _INFLIGHT[:] = [r for r in _INFLIGHT if r() is not None]
        return len(_INFLIGHT)


class AsyncHandle:
    """Awaitable result of an asynchronously issued device collective.

    Lifecycle: the issuing entry point dispatches the jitted program
    (non-blocking — the output array is a future), stamps an
    ``<name>.issue`` span on the dispatch itself, arms the caller's
    watchdog guard if given, and admits the handle to the bounded
    in-flight registry. ``wait()`` blocks until the result is ready,
    disarms the guard, retires the handle, and records the REAL span —
    total issue→ready wall time split into ``wire_exposed_ms`` (time
    the caller actually blocked inside wait) and ``wire_overlapped_ms``
    (wire time hidden behind whatever the caller did in between) —
    feeding the profiling plane's overlap accounting.

    ``value`` is the raw device future: feed it straight into the next
    jitted program for block-free chaining (jax sequences the data
    dependency on-device; no host sync). ``wait()`` is idempotent.
    Dropping a handle without waiting warns and counts
    ``async.dropped_handle`` — the op still completes, but its wire
    time was never accounted and its guard would otherwise leak."""

    def __init__(self, out, *, name: str, nbytes: int, attrs: dict,
                 guard=None, postprocess=None):
        self._out = out
        self._name = name
        self._nbytes = int(nbytes)
        self._attrs = dict(attrs)
        self._guard = guard
        if guard is not None:
            guard.__enter__()
        self._post = postprocess
        self._done = False
        self._result = None
        self._t_issue = time.perf_counter()
        _admit(self)

    @property
    def value(self):
        """The raw device future (pre-postprocess) — for chaining into
        the next device program without a host sync."""
        return self._out

    def ready(self) -> bool:
        if self._done:
            return True
        is_ready = getattr(self._out, "is_ready", None)
        if is_ready is None:
            # pytree: no cheap readiness probe on the container
            leaves = jax.tree_util.tree_leaves(self._out)
            return all(getattr(leaf, "is_ready", lambda: False)()
                       for leaf in leaves)
        return bool(is_ready())

    def wait(self):
        if self._done:
            return self._result
        t_wait = time.perf_counter()
        try:
            jax.block_until_ready(self._out)
        finally:
            self._done = True
            if self._guard is not None:
                self._guard.__exit__(None, None, None)
                self._guard = None
            _retire(self)
        t_done = time.perf_counter()
        total = t_done - self._t_issue
        exposed = t_done - t_wait
        overlapped = max(0.0, total - exposed)
        attrs = dict(self._attrs)
        attrs["wire_exposed_ms"] = exposed * 1e3
        attrs["wire_overlapped_ms"] = overlapped * 1e3
        telemetry.record_span(self._name, total, nbytes=self._nbytes,
                              **attrs)
        _profile.record_overlap(self._name, self._attrs.get("method"),
                                exposed, overlapped)
        post, self._post = self._post, None
        self._result = post(self._out) if post else self._out
        return self._result

    def __del__(self):
        try:
            if not self._done:
                self._done = True
                warnings.warn(
                    f"async collective handle '{self._name}' dropped "
                    "without wait(); result discarded and wire time "
                    "unaccounted", RuntimeWarning, stacklevel=2)
                telemetry.count("async.dropped_handle")
                if self._guard is not None:
                    self._guard.__exit__(None, None, None)
                _retire(self)
        except Exception:
            pass  # interpreter teardown: modules may be half-gone


class AsyncTreeHandle:
    """Composite handle over a sequence of per-bucket
    :class:`AsyncHandle`\\ s (``bucket_allreduce_async``). ``wait()``
    awaits every bucket (oldest first — completion order matches issue
    order on a FIFO fabric) and assembles the final pytree once."""

    def __init__(self, handles, assemble):
        self._handles = list(handles)
        self._assemble = assemble
        self._done = False
        self._result = None

    @property
    def handles(self):
        return tuple(self._handles)

    def ready(self) -> bool:
        return self._done or all(h.ready() for h in self._handles)

    def wait(self):
        if self._done:
            return self._result
        parts = [h.wait() for h in self._handles]
        assemble, self._assemble = self._assemble, None
        self._result = assemble(parts)
        self._done = True
        return self._result


def device_allreduce_async(xs: jax.Array, mesh: Mesh, op: int = SUM,
                           axis: Optional[str] = None,
                           method: str = "auto",
                           wire: Optional[str] = "auto",
                           groups=None, guard=None) -> AsyncHandle:
    """:func:`device_allreduce`, split into issue and await. Same
    dispatch-table resolution, skew agreement boundary (consumed at
    ISSUE time — the schedule is fixed when the program is traced, not
    when the caller waits), cost stamping, and provenance; the span is
    recorded at ``wait()`` with the exposed/overlapped split.

    ``guard`` is an UNENTERED watchdog guard (``Watchdog.guard(...)``)
    covering issue→completion; the handle arms it now and disarms it in
    ``wait()`` (or on drop), so in-flight ops keep their deadline."""
    if axis is None:
        axis = mesh.axis_names[0]
    n = int(np.prod(xs.shape[1:]))
    if _skew.adapt_enabled():
        _skew_sync_point(mesh, axis)
    groups = _topology.resolve_groups(mesh.shape[axis], explicit=groups)
    method, wire = _dispatch_resolve(n, xs.dtype, op, mesh.shape[axis],
                                     method=method, wire=wire,
                                     groups=groups)
    if method not in ("hier", "preagg"):
        groups = None
    adapted = None
    if _skew.adapt_enabled():
        plan = _skew.adapt_plan(method, mesh.shape[axis],
                                n * xs.dtype.itemsize,
                                OP_NAMES.get(op, str(op)), groups=groups,
                                digest=_skew.monitor().applied())
        if plan is not None:
            method, groups = plan["method"], plan["groups"]
            if method == "preagg":
                wire = None
            adapted = f"{plan['kind']}@{plan['laggard']}"
        _skew.note_applied(adapted)
    cost = _profile.record_cost(
        "allreduce", method, wire, n, xs.dtype.itemsize, mesh.shape[axis],
        group_size=len(groups[0]) if groups else None)
    extra = ({"cost_flops": cost["flops"],
              "cost_wire_bytes": cost["wire_bytes"],
              "cost_hops": cost["hops"]} if cost else {})
    if method == "hier" and groups:
        extra["hosts"] = len(groups)
    if adapted:
        extra["adapted"] = adapted
    nbytes = n * xs.dtype.itemsize
    opname = OP_NAMES.get(op, str(op))
    rnd = telemetry.collective_round("allreduce")
    telemetry.count("async.issued", nbytes=nbytes, op=opname,
                    method=method, wire=wire)
    with telemetry.span("allreduce.issue", nbytes=nbytes, op=opname,
                        method=method, wire=wire, round=rnd, **extra):
        with _profile.jit_probe("allreduce", _allreduce_global):
            out = _allreduce_global(xs, mesh, axis, op, method, wire,
                                    groups)
    attrs = {"op": opname, "method": method, "wire": wire, "round": rnd,
             "async": 1}
    attrs.update(extra)
    return AsyncHandle(out, name="allreduce", nbytes=nbytes, attrs=attrs,
                       guard=guard)


@functools.partial(jax.jit, static_argnames=("mesh", "dp_axis", "tp_axis",
                                             "op", "method", "wire"))
def _grad_bucket_allreduce_global(xs, mesh: Mesh, dp_axis: str,
                                  tp_axis: str, op: int, method: str,
                                  wire: str | None):
    # xs: [dp, tp, n] — one flat gradient bucket per (dp, tp) shard;
    # reduce over dp only, each tp shard keeps its own row (model-
    # parallel grads differ per tp shard by construction)
    def per_shard(x):
        flat = x.reshape(-1)  # [1, 1, n] -> [n]
        return _per_shard_allreduce(flat, dp_axis, op, method,
                                    wire)[None, :]
    return unchecked_shard_map(
        per_shard, mesh=mesh, in_specs=P(dp_axis, tp_axis, None),
        out_specs=P(tp_axis, None))(xs)


def grad_bucket_allreduce_async(xs: jax.Array, mesh: Mesh, dp_axis: str,
                                tp_axis: str, op: int = SUM,
                                method: str = "ring",
                                wire: Optional[str] = None,
                                guard=None) -> AsyncHandle:
    """Issue one gradient bucket's data-parallel allreduce without
    blocking — the model pipelines' workhorse. ``xs`` is [dp, tp, n]
    (flat bucket per shard, tp rows distinct); the result is [tp, n],
    reduced over ``dp_axis``. The returned handle's ``value`` feeds the
    parameter-update program directly: consecutive buckets' wire time
    overlaps on-device while the host never syncs."""
    n = int(xs.shape[-1])
    if _skew.adapt_enabled():
        _skew_sync_point(mesh, dp_axis)
    if wire == "auto":
        _, wire = _dispatch_resolve(n, xs.dtype, op,
                                    mesh.shape[dp_axis],
                                    method=method, wire="auto")
    wire = _normalize_wire(_canonical_wire(wire), op, xs.dtype)
    cost = _profile.record_cost("bucket_allreduce", method, wire, n,
                                xs.dtype.itemsize, mesh.shape[dp_axis])
    extra = ({"cost_flops": cost["flops"],
              "cost_wire_bytes": cost["wire_bytes"],
              "cost_hops": cost["hops"]} if cost else {})
    nbytes = n * xs.dtype.itemsize
    opname = OP_NAMES.get(op, str(op))
    rnd = telemetry.collective_round("bucket_allreduce")
    telemetry.count("async.issued", nbytes=nbytes, op=opname,
                    method=method, wire=wire)
    with telemetry.span("bucket_allreduce.issue", nbytes=nbytes, op=opname,
                        method=method, wire=wire, round=rnd, **extra):
        with _profile.jit_probe("bucket_allreduce",
                                _grad_bucket_allreduce_global):
            out = _grad_bucket_allreduce_global(xs, mesh, dp_axis, tp_axis,
                                                op, method, wire)
    attrs = {"op": opname, "method": method, "wire": wire, "round": rnd,
             "async": 1}
    attrs.update(extra)
    return AsyncHandle(out, name="bucket_allreduce", nbytes=nbytes,
                       attrs=attrs, guard=guard)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "op",
                                             "method", "wire"))
def _bucket_flat_global(leaves, mesh: Mesh, axis: str, op: int,
                        method: str, wire: str | None):
    def per_shard(shards):
        flat = jnp.concatenate([x.reshape(-1) for x in shards])
        return _per_shard_allreduce(flat, axis, op, method, wire)
    return unchecked_shard_map(per_shard, mesh=mesh, in_specs=P(axis),
                               out_specs=P())(tuple(leaves))


def bucket_allreduce_async(tree, mesh: Mesh, op: int = SUM,
                           axis: Optional[str] = None,
                           method: str = "auto",
                           wire: Optional[str] = "auto") -> AsyncTreeHandle:
    """:func:`device_allreduce_tree`, issued bucket-by-bucket without
    blocking. Leaves are [p, ...] (the :func:`device_allreduce` layout);
    per-dtype buckets dispatch in REVERSED bucket order — under
    reverse-mode autodiff the late layers' gradients materialize first,
    so issuing their bucket first maximizes the wire time hidden behind
    the remaining compute (DDP ready-order launch). Each bucket's
    method/wire resolves from the dispatch table on the bucket's total
    element count, as in the sync path. ``wait()`` returns the reduced
    pytree (leaf shapes ``leaf.shape[1:]``, replicated)."""
    if axis is None:
        axis = mesh.axis_names[0]
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return AsyncTreeHandle([], lambda parts: tree)
    if _skew.adapt_enabled():
        _skew_sync_point(mesh, axis)
    buckets: dict = {}
    for i, leaf in enumerate(leaves):
        buckets.setdefault(jnp.dtype(leaf.dtype), []).append(i)
    opname = OP_NAMES.get(op, str(op))
    order = list(buckets.items())
    handles = []
    issued_idxs = []
    for dt, idxs in reversed(order):
        sizes = tuple(int(np.prod(leaves[i].shape[1:])) for i in idxs)
        shapes = tuple(leaves[i].shape[1:] for i in idxs)
        n = sum(sizes)
        mth, w = _dispatch_resolve(n, dt, op, mesh.shape[axis],
                                   method=method, wire=wire)
        if mth in ("hier", "preagg"):
            mth = "ring"  # bucket path dispatches flat schedules only
        cost = _profile.record_cost("bucket_allreduce", mth, w, n,
                                    dt.itemsize, mesh.shape[axis])
        extra = ({"cost_flops": cost["flops"],
                  "cost_wire_bytes": cost["wire_bytes"],
                  "cost_hops": cost["hops"]} if cost else {})
        nbytes = n * dt.itemsize
        rnd = telemetry.collective_round("bucket_allreduce")
        telemetry.count("async.issued", nbytes=nbytes, op=opname,
                        method=mth, wire=w)
        bucket_leaves = tuple(leaves[i] for i in idxs)
        with telemetry.span("bucket_allreduce.issue", nbytes=nbytes,
                            op=opname, method=mth, wire=w, round=rnd,
                            buckets=1, leaves=len(idxs), **extra):
            with _profile.jit_probe("bucket_allreduce",
                                    _bucket_flat_global):
                red = _bucket_flat_global(bucket_leaves, mesh, axis, op,
                                          mth, w)

        def _split(red, sizes=sizes, shapes=shapes):
            out, off = [], 0
            for size, shape in zip(sizes, shapes):
                out.append(red[off:off + size].reshape(shape))
                off += size
            return out

        attrs = {"op": opname, "method": mth, "wire": w, "round": rnd,
                 "async": 1}
        attrs.update(extra)
        handles.append(AsyncHandle(red, name="bucket_allreduce",
                                   nbytes=nbytes, attrs=attrs,
                                   postprocess=_split))
        issued_idxs.append(tuple(idxs))

    def assemble(parts):
        out = [None] * len(leaves)
        for idxs, pieces in zip(issued_idxs, parts):
            for i, piece in zip(idxs, pieces):
                out[i] = piece
        return jax.tree_util.tree_unflatten(treedef, out)

    return AsyncTreeHandle(handles, assemble)


def device_hier_allreduce_async(xs: jax.Array, mesh: Mesh, op: int = SUM,
                                axis: Optional[str] = None,
                                groups=None, wire: Optional[str] = None,
                                inter_method: str = "ring",
                                guard=None) -> AsyncHandle:
    """:func:`device_hier_allreduce`, issued without blocking: the three
    phase programs dispatch back-to-back as futures, so phase k+1 is
    enqueued before phase k's wire completes — and, across consecutive
    calls, bucket i's slow inter-host swing/ring phase overlaps bucket
    i+1's intra-host reduce-scatter on-device (the phases touch
    different links, so the fabric genuinely parallelizes them). Each
    phase still gets its own ``.issue`` span, cost stamp, and the shared
    round id; the single watchdog ``guard`` covers issue→completion of
    the whole schedule (per-phase deadlines need a blocking boundary to
    measure against — use the sync variant for that)."""
    if axis is None:
        axis = mesh.axis_names[0]
    p = mesh.shape[axis]
    groups = _topology.resolve_groups(p, explicit=groups)
    if not _topology.is_hierarchical(groups, p):
        if groups and len(groups) == 1:
            wire = None
        flat = "swing" if inter_method == "swing" else "ring"
        return device_allreduce_async(xs, mesh, op=op, axis=axis,
                                      method=flat, wire=wire or "none",
                                      guard=guard)
    adapted = None
    if _skew.adapt_enabled():
        _skew_sync_point(mesh, axis)
        plan = _skew.adapt_plan("hier", p, int(np.prod(xs.shape[1:]))
                                * xs.dtype.itemsize,
                                OP_NAMES.get(op, str(op)), groups=groups,
                                digest=_skew.monitor().applied())
        if plan is not None:
            groups = plan["groups"]
            adapted = f"{plan['kind']}@{plan['laggard']}"
        _skew.note_applied(adapted)
    g, hosts = len(groups[0]), len(groups)
    slots = _topology.slot_rings(groups)
    shape = xs.shape[1:]
    n = int(np.prod(shape))
    itemsize = xs.dtype.itemsize
    if wire == "auto":
        _, wire = _dispatch_resolve(n // g, xs.dtype, op, hosts,
                                    method="ring", wire="auto")
    wire = _normalize_wire(_canonical_wire(wire), op, xs.dtype)
    mult = _wire_pad_mult(wire, p)
    n_pad = n + (-n) % mult
    rnd = telemetry.collective_round("hier_allreduce")
    opname = OP_NAMES.get(op, str(op))

    def _issue(name, phase, nbytes, mth, w, cost_n, cost_axis,
               cost_phase, fn, *args):
        cost = _profile.record_cost(name, mth, w, cost_n, itemsize,
                                    cost_axis, phase=cost_phase,
                                    group_size=g)
        extra = ({"cost_flops": cost["flops"],
                  "cost_wire_bytes": cost["wire_bytes"],
                  "cost_hops": cost["hops"]} if cost else {})
        if adapted:
            extra["adapted"] = adapted
        with telemetry.span(name + ".issue", nbytes=nbytes, op=opname,
                            method=mth, wire=w, round=rnd, phase=phase,
                            hosts=hosts, group_size=g, **extra):
            with _profile.jit_probe(name, fn):
                return fn(*args)

    telemetry.count("async.issued", nbytes=n * itemsize, op=opname,
                    method="hier", wire=wire)
    mid = _issue("hier.reduce_scatter", "reduce_scatter",
                 n * itemsize, "ring", None, n, g, "rs",
                 _hier_rs_global, xs, mesh, axis, op, groups, mult)
    mid = _issue("hier.inter", "inter",
                 (n_pad // g) * itemsize, inter_method, wire,
                 n_pad // g, hosts, None,
                 _hier_inter_global, mid, mesh, axis, op, slots, wire,
                 inter_method)
    out = _issue("hier.allgather", "allgather",
                 n * itemsize, "ring", None, n_pad, g, "ag",
                 _hier_ag_global, mid, mesh, axis, groups)
    attrs = {"op": opname, "method": "hier", "wire": wire, "round": rnd,
             "hosts": hosts, "group_size": g, "async": 1}
    if adapted:
        attrs["adapted"] = adapted
    return AsyncHandle(out, name="hier_allreduce", nbytes=n * itemsize,
                       attrs=attrs, guard=guard,
                       postprocess=lambda o: o[:n].reshape(shape))
