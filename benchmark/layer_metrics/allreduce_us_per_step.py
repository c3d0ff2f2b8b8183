"""Microseconds a step of the compiled epoch spends in all-reduce
operations (the `psum` of `BoundSync._one_step`): their self time inside
the epoch program over the steps the traced window holds, first device.
The wait for the slowest chip is inside the operation."""

from benchmark import reduce_trace


def read(run):
    if run.trace is None:
        return None
    return reduce_trace.class_us_per_step(
        run.trace["devices"][run.trace["detail_device"]], "allreduce")
