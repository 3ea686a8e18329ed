"""Device time per round of the dense histogram build kernel alone,
found on the trace by its stable name rabit_hist_build_dense: the union
of its op intervals in the window, without the hi/lo prep and the
result's reshaping that hist_build_ms also counts. None where no op of
that name ran."""

from bench_harness import program_spans


def read(run):
    return program_spans.kernel_ms(run, "histogram", "rabit_hist_build_dense")
