"""Bus bandwidth as nccl-tests' all_reduce_perf counts it: payload bytes
per rank x 2(p-1)/p, summed over every allreduce of the window, over the
window's wall time, in GB/s."""

from bench_harness import work


def read(run):
    if run.entry != "allreduce":
        return None
    bus = sum(work.allreduce_bus_bytes(b, run.chips)
              for r in run.records for b in r["bytes"])
    return bus / run.window_s / 1e9
