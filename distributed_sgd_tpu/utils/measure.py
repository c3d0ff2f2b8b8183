"""Wall-clock measurement spans.

TPU-native equivalent of the reference's ``Measure`` helpers
(utils/Measure.scala:11-35): `duration` returns (result, seconds),
`duration_log` logs a named span, and `span` is a context manager with
three sinks: it feeds the metrics registry; when the distributed tracer
is active (trace/, DSGD_TRACE) it ALSO opens a trace span; and inside a
`jax.profiler` session it is a `TraceAnnotation` on the profiler's clock.
One instrumentation point serves the aggregate surface (histograms ->
exporters), the causal one (span timelines -> Perfetto) and the device
trace (host spans against device gaps).  For device work, callers must
account for JAX async dispatch themselves (block_until_ready) — the
trainer does this at epoch boundaries.

Histogram-name cardinality is bounded: span names outside
`SPAN_NAME_ALLOWLIST` warn once each, and once `MAX_DISTINCT_SPAN_NAMES`
distinct names have been recorded, further unknown names aggregate under
``span.other`` — a caller that interpolates ids into span names must not
grow the exporter payload without bound.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Tuple, TypeVar

from distributed_sgd_tpu import trace as trace_mod

T = TypeVar("T")

log = logging.getLogger("dsgd.measure")

# Known span names (docs/OBSERVABILITY.md); additions belong here so the
# instrument-name consistency test (tests/test_observability.py) and the
# dashboards agree on spelling.
SPAN_NAME_ALLOWLIST = frozenset({
    "slave.grad.compute",
    "slave.grad.encode",
    "slave.agg.reduce",
    "slave.async.gossip",
    "serve.predict.decode",
    "serve.predict.queue",
    "serve.batch.execute",
    "route.predict",
    "ckpt.save",
    "ckpt.restore",
    "trainer.epoch",
    "trainer.evaluate",
    "trainer.evaluate.dispatch",
    "trainer.evaluate.wait",
    "trainer.evaluate.pull",
    "trainer.evaluate.reg",
    "trainer.bookkeeping",
    "trainer.criterion",
    "slave.async.iteration",
    "slave.async.drain",
    "slave.async.step",
    "slave.async.apply",
    "slave.async.pull",
    "slave.async.push",
    "master.async.check",
    "sync.bind.place",
    "bind.margin_plan",
})
MAX_DISTINCT_SPAN_NAMES = 64
SPAN_OVERFLOW_NAME = "other"

_seen_names: set = set()
_warned_names: set = set()
_names_lock = threading.Lock()


def _bounded_name(name: str) -> str:
    """Cardinality guard for the `span.<name>` histogram family."""
    # lock-free fast path: after warm-up every hot-path span name is
    # already a member, and a GIL-atomic set read needs no lock (a racing
    # first-add just falls through to the locked slow path)
    if name in _seen_names:
        return name
    with _names_lock:
        if name in _seen_names:
            return name
        if name not in SPAN_NAME_ALLOWLIST and name not in _warned_names:
            if len(_warned_names) < 2 * MAX_DISTINCT_SPAN_NAMES:
                _warned_names.add(name)
                log.warning(
                    "span name %r is not in SPAN_NAME_ALLOWLIST "
                    "(utils/measure.py); dashboards will not know it, and "
                    "unknown names beyond %d aggregate under 'span.%s'",
                    name, MAX_DISTINCT_SPAN_NAMES, SPAN_OVERFLOW_NAME)
        if (name not in SPAN_NAME_ALLOWLIST
                and len(_seen_names) >= MAX_DISTINCT_SPAN_NAMES):
            return SPAN_OVERFLOW_NAME
        _seen_names.add(name)
        return name


class ProfileWindow:
    """Windowed ``jax.profiler`` capture shared by the RPC worker and the
    serving engine (DSGD_PROFILE_DIR, docs/OBSERVABILITY.md): `tick()` is
    called at the START of each dispatch; the capture opens on the first
    tick and closes on the first tick PAST the window, so all `steps`
    dispatch bodies land inside it (stopping at the Nth tick's start
    would capture only N-1).  `close()` finishes a still-open capture at
    shutdown (the run never reached `steps + 1` dispatches).  Thread-safe;
    never raises — profiling must not break the work it observes."""

    def __init__(self, profile_dir, steps: int, logger=None, what: str = "dispatches"):
        self.dir = profile_dir
        self.left = max(1, int(steps)) if profile_dir else 0
        self.started = False
        self.stopped = False
        self.what = what
        self._lock = threading.Lock()
        self._log = logger or log

    def tick(self) -> None:
        if self.stopped or (self.left <= 0 and not self.started):
            return
        with self._lock:
            if self.stopped:
                return
            try:
                import jax

                if not self.started:
                    jax.profiler.start_trace(self.dir)
                    self.started = True
                    self._log.info("profiling first %d %s -> %s",
                                   self.left, self.what, self.dir)
                elif self.left <= 0:
                    # first dispatch past the window: the previous `steps`
                    # bodies are complete — close the capture
                    self.stopped = True
                    jax.profiler.stop_trace()
                    self._log.info("profiler trace written to %s", self.dir)
                    return
                self.left -= 1
            except Exception as e:  # noqa: BLE001 - profiling is best-effort
                self.left = 0
                self.stopped = True
                self._log.warning("jax.profiler capture failed: %s", e)

    def close(self) -> None:
        with self._lock:
            if self.started and not self.stopped:
                self.stopped = True
                try:
                    import jax

                    jax.profiler.stop_trace()
                    self._log.info("profiler trace written to %s", self.dir)
                except Exception as e:  # noqa: BLE001
                    self._log.warning("jax.profiler stop failed: %s", e)


def duration(fn: Callable[[], T]) -> Tuple[T, float]:
    """Run `fn`, return (result, elapsed_seconds). Measure.scala:11-16."""
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def duration_log(name: str, fn: Callable[[], T], logger=None) -> T:
    """Run `fn` and log '<name>: Xs'. Measure.scala:18-24."""
    out, secs = duration(fn)
    (logger or log).info("%s (%.3fs)", name, secs)
    return out


_TraceAnnotation = None


def _profiler_annotation():
    """`jax.profiler.TraceAnnotation`, imported on first use: launchers
    import this module and must not import jax (PR 21)."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation


class span:
    """Context-manager span, the program's one instrumentation point, with
    three sinks:

    - a histogram sample ``span.<name>`` (always, unless ``histogram=False``)
      and a debug log line;
    - when the distributed tracer is active (trace/, DSGD_TRACE), a trace
      span: child of the thread's current trace context, or a new sampled
      root.  Pass ``root=False`` for helper spans that only make sense
      INSIDE a trace (e.g. the worker's compute/encode breakdown of a
      Gradient call): with no active context they stay no-op instead of
      fabricating an orphan one-span trace per unsampled call;
    - inside a ``jax.profiler`` session, a ``TraceAnnotation`` on the
      profiler's clock: an event on the calling thread's line of
      ``/host:CPU``, nested by time under whatever span the thread already
      holds, so the span can be laid against the device's gaps.

    `trace_args` (``epoch=3``, ``worker=1``, ``node="w0:4001"``) become the
    trace span's attributes and the annotation's stats.  ``histogram=False``
    is for spans that are PHASES of a loop iteration whose whole already
    has a histogram: with the tracer and the profiler off such a span
    allocates nothing beyond itself and records nothing (budget in
    PERF.md: under 1.5 us; a full span under 5 us).  Names never start
    with ``bench.`` or ``$``: the benchmark's reducer reads those prefixes
    as its own marks and as python frames.

    ``with span(...) as s`` binds the trace span (``NOOP_SPAN`` when the
    tracer is off)."""

    __slots__ = ("name", "_logger", "_metrics", "_root", "_histogram",
                 "_args", "_tspan", "_annotation", "_t0")

    def __init__(self, name: str, logger=None, metrics=None, root: bool = True,
                 histogram: bool = True, **trace_args):
        self.name = name
        self._logger = logger
        self._metrics = metrics
        self._root = root
        self._histogram = histogram
        self._args = trace_args

    def __enter__(self):
        if self._histogram:
            self._t0 = time.perf_counter()
        annotation = _TraceAnnotation or _profiler_annotation()
        if annotation.is_enabled():
            self._annotation = annotation(self.name, **self._args)
            self._annotation.__enter__()
        else:
            self._annotation = None
        if trace_mod._TRACER is None:  # the tracer's own zero-cost gate
            self._tspan = None
            return trace_mod.NOOP_SPAN
        self._tspan = trace_mod.span(self.name, root=self._root, **self._args)
        return self._tspan.__enter__()

    def __exit__(self, etype, evalue, tb):
        if self._tspan is not None:
            self._tspan.__exit__(etype, evalue, tb)
        if self._annotation is not None:
            self._annotation.__exit__(etype, evalue, tb)
        if self._histogram:
            secs = time.perf_counter() - self._t0
            (self._logger or log).debug("%s (%.3fs)", self.name, secs)
            metrics = self._metrics
            if metrics is None:
                from distributed_sgd_tpu.utils.metrics import global_metrics

                metrics = global_metrics()
            metrics.histogram("span." + _bounded_name(self.name)).record(secs)
        return False
