"""Worker dispatches a second over the window: `slave.async.batch` counts
local steps, a dispatch runs `steps_per_dispatch` of them."""


def read(run):
    c = run.counters.get("slave.async.batch")
    k = run.engine.get("steps_per_dispatch")
    if c is None or not k or not run.window_seconds:
        return None
    return (c["end"] - c["start"]) / k / run.window_seconds
