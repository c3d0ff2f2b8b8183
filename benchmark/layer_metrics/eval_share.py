"""Share of the window's wall time that is not the compiled epoch: per
epoch period (boundary to boundary) the fit's own `epoch_seconds` covers
the dispatch of `BoundSync.epoch` to `block_until_ready`; the rest is the
train and test evaluation, the log line and the loop's bookkeeping."""


def read(run):
    if not run.periods:
        return None
    wall = sum(p["end"] - p["start"] for p in run.periods)
    work = sum(p["work_s"] for p in run.periods)
    return 100.0 * (1.0 - work / wall)
