"""1 - (union of op intervals) / traced window, on the busiest device,
in the histogram cells."""

from bench_harness import readers


def read(run):
    return readers.idle_share(run, "histogram")
