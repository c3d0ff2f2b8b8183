"""Milliseconds per epoch in which nothing ran on the worst device: the
idle seconds of the traced window outside the epoch program (from the end
of `jit__epoch_shard` through evaluation and the loop to the boundary)
over the number of epoch programs in the window.  From the trace alone:
the window opens inside an epoch program (the reducer refuses a trace that
does not) and closes at a boundary, so it holds every gap of the epochs it
counts; time inside a program's event counts as busy."""


def read(run):
    if run.trace is None:
        return None
    dev = run.trace["devices"][run.trace["worst_device"]]
    if not dev.get("program") or not dev["program"]["runs"]:
        return None
    return 1e3 * dev["between"]["idle_s"] / dev["program"]["runs"]
