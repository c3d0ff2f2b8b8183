"""Milliseconds the first device is busy outside the epoch program per
epoch: the two evaluation programs and the fit loop's small programs,
from the trace alone (busy seconds of the window outside
`jit__epoch_shard` events over the number of such events in the window)."""


def read(run):
    if run.trace is None:
        return None
    dev = run.trace["devices"][run.trace["detail_device"]]
    if not dev.get("program") or not dev["program"]["runs"]:
        return None
    return 1e3 * dev["between"]["busy_s"] / dev["program"]["runs"]
