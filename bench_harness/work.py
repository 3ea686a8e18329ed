"""Work counts of one call, from the shapes the cell hands to the entry.

Kept with the benchmark so that every PR counts the same work the same
way, whatever implements it.
"""

from __future__ import annotations

# bytes per histogram entry as handed to distributed_histogram:
# int32 bin id + f32 grad + f32 hess
HIST_ENTRY_BYTES = 4 + 4 + 4
# the [nbins, 2] f32 (sum_g, sum_h) result
HIST_BIN_BYTES = 2 * 4


def hist_bytes(entries: int, nbins: int) -> int:
    """Least HBM traffic of one histogram build: every input entry read
    once and the result written once."""
    return entries * HIST_ENTRY_BYTES + nbins * HIST_BIN_BYTES


def allreduce_bus_bytes(payload_bytes: float, p: int) -> float:
    """Bus bytes of one allreduce of ``payload_bytes`` per rank over
    ``p`` ranks, as nccl-tests' ``all_reduce_perf`` counts them:
    S * 2(p-1)/p. It is also the least a rank must send (and receive)
    in any allreduce schedule."""
    return payload_bytes * 2.0 * (p - 1) / p
