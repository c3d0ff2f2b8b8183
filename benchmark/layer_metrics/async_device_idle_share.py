"""Share of the traced window (the first two seconds of the asynchronous
window, steady state throughout) in which nothing ran on the worst device,
from the trace alone."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace["idle_share"]
