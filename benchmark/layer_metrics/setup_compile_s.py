"""Seconds the process spent in backend compiles and persistent-cache
retrievals before the window: the sum over `compile_cache.compiles()`
(one entry per compile, from jax's own duration events: which function,
how long, hit or miss) of the entries that ended before `window_start`.
None on a commit whose `compile_cache` keeps no such list."""


def read(run):
    from distributed_sgd_tpu import compile_cache

    compiles = getattr(compile_cache, "compiles", None)
    if compiles is None:
        return None
    return sum(seconds for at, _fun, seconds, _hit in compiles() if at <= run.window_start)
