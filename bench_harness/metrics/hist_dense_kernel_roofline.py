"""HBM bound of the build over the dense kernel's own device time: the
bytes of the inputs as each call handed them to the entry (4 B per entry
+ 8 B per row in the dense layout) and the f32 result, at the chip's HBM
peak, over the busy time of the ops named rabit_hist_build_dense. None
where no op of that name ran."""

from bench_harness import program_spans, work


def read(run):
    ms = program_spans.kernel_ms(run, "histogram", "rabit_hist_build_dense")
    if ms is None:
        return None
    nbytes = sum(work.hist_bytes(b, r["nbins"])
                 for r in run.records for b in r["bytes"])
    bound_s = nbytes / run.peak("hbm_bytes_per_s")
    return 100.0 * bound_s / (ms * len(run.records) / 1e3)
