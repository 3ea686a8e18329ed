"""HBM bound of the build over its device time: the bytes of the inputs
as handed to the entry (int32 bin + f32 grad + f32 hess per entry) and
the f32 result, at the chip's HBM peak, over hist_build_ms."""

from bench_harness import readers, work


def read(run):
    busy = readers.busy_ns(run, "histogram", "compute")
    if busy is None:
        return None
    nbytes = sum(work.hist_bytes(e, r["nbins"])
                 for r in run.records for e in r["entries"])
    bound_s = nbytes / run.peak("hbm_bytes_per_s")
    return 100.0 * bound_s / (busy / 1e9)
