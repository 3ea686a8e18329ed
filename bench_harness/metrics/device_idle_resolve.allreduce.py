"""% of the traced window in which the busiest device runs no op while
the host is inside rabit.allreduce.resolve, the phases mapped onto the
trace's clock by the rounds' bench.round spans."""

from bench_harness import program_spans


def read(run):
    return program_spans.idle_inside(run, "allreduce",
                                     "rabit.allreduce.resolve")
