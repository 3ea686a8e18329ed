"""95th percentile of one round's caller-side time, in the cells this
metric's entry in BENCHMARK.json lists (one metric per cell, so that each
has a bound from its own spread)."""

from bench_harness import readers


def read(run):
    return readers.round_p95_ms(run)
