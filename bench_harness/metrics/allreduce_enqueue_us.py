"""Median over the window's device_allreduce calls of the program's host
phase rabit.allreduce.enqueue: the jitted allreduce program's call until
it returns."""

from bench_harness import program_spans


def read(run):
    return program_spans.median_us(run, "allreduce",
                                   "rabit.allreduce.enqueue")
