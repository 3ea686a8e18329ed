"""Work counts of one call, from what the cell hands to the entry.

Kept with the benchmark so that every PR counts the same work the same
way, whatever implements it.
"""

from __future__ import annotations

# the [nbins, 2] f32 (sum_g, sum_h) result
HIST_BIN_BYTES = 2 * 4


def hist_bytes(input_bytes: int, nbins: int) -> int:
    """Least HBM traffic of one histogram build: its inputs, as handed to
    distributed_histogram, read once and the result written once. The
    histogram entry records each call's input bytes from the arrays it
    hands: 12 B per entry in the entries layout (int32 bin, f32 grad, f32
    hess), 4 B per entry and 8 B per row in the dense layout."""
    return input_bytes + nbins * HIST_BIN_BYTES


def allreduce_bus_bytes(payload_bytes: float, p: int) -> float:
    """Bus bytes of one allreduce of ``payload_bytes`` per rank over
    ``p`` ranks, as nccl-tests' ``all_reduce_perf`` counts them:
    S * 2(p-1)/p. It is also the least a rank must send (and receive)
    in any allreduce schedule."""
    return payload_bytes * 2.0 * (p - 1) / p
