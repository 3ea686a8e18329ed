"""HBM bound of the build over its device time: the bytes of the inputs
as each call handed them to the entry (recorded per call: 12 B per entry
in the entries layout, 4 B per entry + 8 B per row in the dense layout)
and the f32 result, at the chip's HBM peak, over hist_build_ms."""

from bench_harness import readers, work


def read(run):
    busy = readers.busy_ns(run, "histogram", "compute")
    if busy is None:
        return None
    nbytes = sum(work.hist_bytes(b, r["nbins"])
                 for r in run.records for b in r["bytes"])
    bound_s = nbytes / run.peak("hbm_bytes_per_s")
    return 100.0 * bound_s / (busy / 1e9)
