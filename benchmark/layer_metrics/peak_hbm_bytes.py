"""`memory_stats()["peak_bytes_in_use"]` of the fullest device after the
window: rows, replicas and whatever set-up left behind."""

from benchmark.harness import memory_peak_bytes


def read(run):
    return memory_peak_bytes(run.ctx.devices)
