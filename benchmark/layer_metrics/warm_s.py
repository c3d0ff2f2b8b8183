"""Seconds of warm-up inside the one fit, from the program's log record
that follows its bind to the start of the window: compiling (on a cold
cache) or loading the epoch and evaluation programs, and the warm epochs
(sync) or the first loss check and local steps (Hogwild)."""


def read(run):
    return run.ctx.setup.get("warm_s")
