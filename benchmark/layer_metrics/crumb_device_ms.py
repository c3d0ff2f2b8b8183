"""Milliseconds per epoch the first device is busy outside the epoch program
AND outside the evaluation programs: the fit loop's eager programs (the
slices behind `float()`, the convert / power / sum of `lam*||w||^2`, the
key's fold).  The `boundary_spans:` line splits it by program and by the
host phase (`trainer.evaluate.wait`, `.pull`, `.reg` ...)
whose span holds the program's device event."""

from benchmark import boundary_spans


def read(run):
    return boundary_spans.metric(run, "crumb_device_ms")
