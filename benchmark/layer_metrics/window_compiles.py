"""Persistent-cache look-ups (hits + misses, `compile_cache.counts()`)
between the window's start and its end.  A hit is still a trace and a load
inside the window; the expected value is 0."""


def read(run):
    start, end = run.compiles
    if start is None or end is None:
        return None
    return end - start
