"""Persistent compile cache + AOT warmup: the elastic spin-up fast path.

The reference's only join story is a registration retry loop
(Slave.scala:40-77) — a joining worker pays full data load and, in this
JAX reproduction, full XLA compilation before its first contribution.
That makes elastic membership (docs/ELASTICITY.md) and autoscaling
latency-bound on SPIN-UP rather than on steady-state math: the kernels a
fresh worker compiles are byte-identical to the ones every previous
worker already compiled.

- **persistent cache** — every entry point (main.py, bench.py,
  chip_smoke.py, the benches' ``__main__``s) calls ``place()`` before its
  first jit.  The directory is placed from OUTSIDE: when
  ``JAX_COMPILATION_CACHE_DIR`` is set, jax's own reading of it stands
  and nothing here touches the setting; otherwise the cache lives at the
  fixed ``<checkout>/.jax_cache`` (the path is part of what makes two
  runs hit the same entries, so it never contains a temp dir, pid or
  time).  The min-compile-time/min-size floors are dropped so every
  training/serving kernel is eligible.  XLA backend compiles are keyed by
  the lowered HLO — its metadata (scope names, source lines; file names
  from the checkout's root) included, so an executable read back never
  shows another version's names in a profile — so a joining worker, a restarted master, or a fresh
  serve replica re-compiling a known flagship shape reads the executable
  from disk instead of re-running XLA.  jax's own monitoring events feed
  the ``compile.cache.hits``/``compile.cache.misses`` counters
  (utils/metrics.py), so the instruments cover every compile in the
  process — not just the warmed ones.  jax's duration events say WHICH
  function compiled and for how long: each backend compile (or cache
  retrieval) is one sample of the ``compile.seconds`` histogram, one INFO
  record on ``dsgd.compile`` and one entry of ``compiles()``.
- **AOT warmup** (``DSGD_COMPILE_CACHE=1``) — ``warmup_async(name,
  thunks)`` runs a role's flagship compile thunks on ONE background
  daemon thread at bind/build time (worker ``_grad_fn``/``_window_fn``
  per capacity bucket and the hier psum kernels via
  ``WorkerNode.warmup_thunks``, the mesh BoundSync epoch program via
  ``BoundSync.warmup_thunks``, the serving per-bucket Predict via
  ``PredictEngine.warmup_thunks``) so a joining node compiles while it
  registers/loads instead of under its first request.  Worker/serving
  thunks execute the real jitted callable once on inert zero inputs, so
  they populate the IN-PROCESS dispatch cache too: the first real
  dispatch after warmup performs no tracing at all
  (tests/test_compile_cache.py proves it with a poisoned-trace spy).

The library stays passive: nothing here runs until an entry point calls
``place()`` — a ``WorkerNode`` built in a test configures nothing, starts
no warmup thread and writes no file (asserted by
tests/test_compile_cache.py and ``bench.py --spinup``).

Concurrency: a real dispatch arriving while its shape is still warming is
safe — both threads call the same jitted callable and jax serializes /
deduplicates the underlying executable; the race costs at most one
redundant compile (which the persistent cache then absorbs), never a
wrong result.  ``python bench.py --spinup`` gates the payoff: >= 2x
faster time-to-first-contribution for a warm-cache join vs a cold one.
"""

from __future__ import annotations

import logging
import os
import re
import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

log = logging.getLogger("dsgd.compile_cache")
compile_log = logging.getLogger("dsgd.compile")  # one record per compile

# one warmup thunk: (label, zero-arg callable that triggers the compile)
WarmupThunk = Tuple[str, Callable[[], object]]

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")

_placed_dir: Optional[str] = None
_warmup = False
_listener_installed = False
# (perf_counter at its end, function, seconds, served from the persistent
# cache) of the process's first MAX_COMPILES compiles since place()
MAX_COMPILES = 256
_compiles: List[Tuple[float, str, float, bool]] = []


def cache_dir() -> Optional[str]:
    """The active cache directory; None until an entry point placed it."""
    return _placed_dir


def warmup_enabled() -> bool:
    """True when the entry point asked for the AOT warmup pass."""
    return _warmup


def place(warmup: bool = False, metrics=None) -> str:
    """Turn on jax's persistent compilation cache and start counting its
    hits/misses; returns the directory in use.  Must run BEFORE the first
    jit dispatch of the process; idempotent.  `warmup` additionally arms
    the bind/build-time AOT warmup pass (DSGD_COMPILE_CACHE).
    """
    global _placed_dir, _warmup
    import jax

    from distributed_sgd_tpu.utils import metrics as metrics_mod

    from_env = bool(os.environ.get(ENV_DIR))
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # every kernel is spin-up-relevant: drop the "only cache slow/large
    # compiles" floors so the per-capacity worker kernels (fast compiles
    # individually, the whole set is what a join waits on) are eligible
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # An executable read back from the cache carries the metadata (the
    # jax.named_scope paths, the source lines) of whichever checkout
    # compiled it first, and by default jax leaves metadata out of the key:
    # a profile of THIS code would then show another version's names (seen
    # on the v5e, PR 24: the parent commit's trace held this commit's
    # scopes).  The profiler and the benchmark's by-name metrics read those
    # names, so they are part of the key; the same code still hits.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    # ... but not WHERE the checkout lies: source files are named from its
    # root, so the same code unpacked at another path still hits
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(CHECKOUT + os.sep))
    _placed_dir = jax.config.jax_compilation_cache_dir
    _warmup = _warmup or bool(warmup)
    _install_listener(metrics or metrics_mod.global_metrics())
    log.info("persistent compile cache: %s (%s)%s", _placed_dir,
             f"from {ENV_DIR}" if from_env else "default",
             ", AOT warmup on" if _warmup else "")
    return _placed_dir


def counts(metrics=None) -> Tuple[int, int]:
    """(hits, misses) of the persistent cache in this process so far."""
    from distributed_sgd_tpu.utils import metrics as metrics_mod

    m = metrics or metrics_mod.global_metrics()
    return (m.counter(metrics_mod.COMPILE_CACHE_HITS).value,
            m.counter(metrics_mod.COMPILE_CACHE_MISSES).value)


def compiles() -> List[Tuple[float, str, float, bool]]:
    """Which functions compiled so far, in order: (perf_counter at the
    compile's end, function as jax names it — ``jit(_epoch_shard)`` —,
    seconds, True where the persistent cache served it)."""
    return list(_compiles)


def _install_listener(metrics) -> None:
    """Feed jax's compilation-cache monitoring events into our counters,
    and its compile durations into `compiles()`.  Registered once per
    process."""
    global _listener_installed
    if _listener_installed:
        return
    from jax._src import monitoring

    from distributed_sgd_tpu.utils import metrics as metrics_mod

    hits = metrics.counter(metrics_mod.COMPILE_CACHE_HITS)
    misses = metrics.counter(metrics_mod.COMPILE_CACHE_MISSES)

    seconds = metrics.histogram(metrics_mod.COMPILE_SECONDS)
    served = threading.local()  # this thread's compile was a cache hit

    def _on_event(event: str, **kwargs) -> None:
        if event.endswith("/cache_hits"):
            hits.increment()
            served.hit = True
        elif event.endswith("/cache_misses"):
            misses.increment()

    def _on_duration(event: str, secs: float, **kwargs) -> None:
        # jax 0.9.0: one backend_compile_duration per compile, around the
        # cache look-up too, so a hit's event precedes it on the same thread
        if not event.endswith("/backend_compile_duration"):
            return
        hit, served.hit = getattr(served, "hit", False), False
        fun = str(kwargs.get("fun_name", "?"))
        seconds.record(secs)
        if len(_compiles) < MAX_COMPILES:
            _compiles.append((time.perf_counter(), fun, float(secs), hit))
        compile_log.info("compiled %s in %.3fs (%s)", fun, secs,
                         "persistent-cache hit" if hit else "miss")

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _listener_installed = True


def run_warmup(name: str, thunks: Sequence[WarmupThunk],
               metrics=None) -> int:
    """Run `thunks` synchronously; returns how many compiled cleanly.

    One failed thunk never kills the rest (or the caller): warmup is an
    optimization, and the dispatch path compiles lazily exactly as it
    would have without it — the failure is logged and counted."""
    from distributed_sgd_tpu.utils import metrics as metrics_mod

    if metrics is None:
        metrics = metrics_mod.global_metrics()
    t0 = time.perf_counter()
    done = 0
    for label, thunk in thunks:
        t1 = time.perf_counter()
        try:
            thunk()
        except Exception as e:  # noqa: BLE001 - see docstring
            metrics.counter(metrics_mod.COMPILE_WARMUP_ERRORS).increment()
            log.warning("warmup %s/%s failed: %s", name, label, e)
            continue
        done += 1
        metrics.counter(metrics_mod.COMPILE_WARMUP_KERNELS).increment()
        log.info("warmed %s/%s in %.3fs", name, label,
                 time.perf_counter() - t1)
    metrics.gauge(metrics_mod.COMPILE_WARMUP_SECONDS).set(
        time.perf_counter() - t0)
    return done


def warmup_async(name: str, thunks: Sequence[WarmupThunk],
                 metrics=None) -> Optional[threading.Thread]:
    """Start the AOT warmup pass for one role on a background daemon
    thread (None when there is nothing to warm).  The caller keeps
    spinning up — registration, data load, serving bind — while the
    flagship shapes compile; join() the returned thread to run warmup
    synchronously (the spin-up bench's warm path does, so its measured
    first contribution is the pure post-warmup cost)."""
    thunks = list(thunks)
    if not thunks:
        return None
    t = threading.Thread(
        target=run_warmup, args=(name, thunks, metrics),
        daemon=True, name=f"warmup-{name}")
    t.start()
    return t


def cache_file_count() -> int:
    """Number of entries in the placed cache dir (0 when unplaced/empty);
    the cross-process reuse tests assert this stops growing on a rerun."""
    if _placed_dir is None or not os.path.isdir(_placed_dir):
        return 0
    return len(os.listdir(_placed_dir))
