"""1 - (union of op intervals) / traced window, on the busiest device,
in the allreduce cells."""

from bench_harness import readers


def read(run):
    return readers.idle_share(run, "allreduce")
