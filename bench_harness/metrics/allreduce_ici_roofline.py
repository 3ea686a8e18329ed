"""Least time of any allreduce schedule over the measured device time:
2(p-1)/p x payload bytes over the chip's ICI peak, over
allreduce_device_ms. No schedule sends less, so it cannot pass 100%."""

from bench_harness import readers, work


def read(run):
    busy = readers.busy_ns(run, "allreduce")
    if busy is None:
        return None
    bus = sum(work.allreduce_bus_bytes(b, run.chips)
              for r in run.records for b in r["bytes"])
    return 100.0 * (bus / run.peak("ici_bytes_per_s")) / (busy / 1e9)
