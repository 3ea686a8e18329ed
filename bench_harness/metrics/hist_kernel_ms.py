"""Device time per round of the histogram build kernel alone, found on
the trace by its stable name rabit_hist_build: the union of its op
intervals in the window, without the padding and hi/lo prep around it
that hist_build_ms also counts."""

from bench_harness import program_spans


def read(run):
    return program_spans.kernel_ms(run, "histogram", "rabit_hist_build")
