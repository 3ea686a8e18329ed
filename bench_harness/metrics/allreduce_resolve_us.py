"""Median over the window's device_allreduce calls of the program's host
phase rabit.allreduce.resolve: skew sync, topology, dispatch resolve
(its table lookup included) and the skew plan."""

from bench_harness import program_spans


def read(run):
    return program_spans.median_us(run, "allreduce",
                                   "rabit.allreduce.resolve")
