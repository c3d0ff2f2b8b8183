"""Mean microseconds of a `slave.async.pull` span (`np.asarray(delta)` in
`_Worker._iteration`: the worker's wait for the device) over the
iterations that lie whole inside the traced window, all workers."""

from benchmark import program_spans


def read(run):
    spans = program_spans.part(run, "async")
    if not spans:
        return None
    return spans["phase_us"]["slave.async.pull"]
