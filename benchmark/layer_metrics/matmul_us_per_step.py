"""Microseconds a step of the compiled epoch spends in matrix-multiply-class
operations (convolution, dot, and the output fusions the TPU compiler
roots at one: the one-hot gather and scatter of `ops/mxu.py`): their self
time inside the epoch program over the steps the traced window holds,
first device.  Evaluation's matmuls lie outside the epoch program and are
not counted (`eval_device_ms` has them)."""

from benchmark import reduce_trace


def read(run):
    if run.trace is None:
        return None
    return reduce_trace.class_us_per_step(
        run.trace["devices"][run.trace["detail_device"]], "matmul", absent=0.0)
