"""Median host time of one device_allreduce call until it returns: its
host code (skew, topology, dispatch resolve, profile) and the enqueue."""

from bench_harness import readers


def read(run):
    return readers.dispatch_us(run, "allreduce")
