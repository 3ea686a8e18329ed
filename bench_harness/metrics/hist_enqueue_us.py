"""Median over the window's distributed_histogram calls of the program's
host phase rabit.hist.enqueue: the jitted program's call until it
returns."""

from bench_harness import program_spans


def read(run):
    return program_spans.median_us(run, "histogram", "rabit.hist.enqueue")
