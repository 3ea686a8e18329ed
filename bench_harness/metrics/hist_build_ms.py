"""Device time per round of every op that is no collective (the Pallas
kernel, the hi/lo prep and the padding), as the union of their trace
intervals on the busiest device, over the rounds of the traced window."""

from bench_harness import readers


def read(run):
    return readers.per_round_ms(run, "histogram", "compute")
