"""Device time per round of the allreduce programs (hops and the adds
between them) on the busiest chip: union of its op intervals over the
rounds of the traced window."""

from bench_harness import readers


def read(run):
    return readers.per_round_ms(run, "allreduce")
