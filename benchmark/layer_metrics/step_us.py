"""Microseconds per SGD step inside the compiled epoch: the median of the
fit's own `epoch_seconds` over the window, over `steps_per_epoch`."""

import statistics


def read(run):
    steps = run.engine.get("steps_per_epoch")
    if not run.periods or not steps:
        return None
    return 1e6 * statistics.median(p["work_s"] for p in run.periods) / steps
