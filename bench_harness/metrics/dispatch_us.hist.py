"""Median host time of one distributed_histogram call until it returns
(the enqueue: the jit entry's host work, not the device's)."""

from bench_harness import readers


def read(run):
    return readers.dispatch_us(run, "histogram")
