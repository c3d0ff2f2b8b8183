"""The epoch boundary by name: what runs on the device between two epoch
programs of a sync fit, piece by piece, and which phase of the host's
`BoundSync.evaluate` the device stood idle in.  The helper the boundary's
per-layer metrics share (`eval_margins_ms`, `eval_rows_ms`, `eval_other_ms`,
`crumb_device_ms`, `boundary_programs`, `eval_pull_idle_ms`,
`eval_reg_idle_ms`); parsed once per run, cached on the run, printed as one
`boundary_spans:` line before the result.

It adds no definition of its own.  Window, devices and the count of epoch
programs (`runs`) are `run.trace`'s, so a number here divides by what
`eval_device_ms` and `boundary_idle_ms` divide by; scopes, spans, self
times and gaps are `program_spans`' and `reduce_trace`'s.

What it reads, beyond what `program_spans` reads:

- the evaluation programs' own scopes (PR 34): inside `dsgd.eval`,
  `dsgd.eval_rows` is a chunk's fetch (the slices of the resident rows and
  labels, and whatever re-layout the compiler puts there), `dsgd.margins` /
  `dsgd.onehot` the model's forward product, `dsgd.eval_reduce` the losses,
  the hits and their sums.  An operation counts for an evaluation program
  where it starts inside a `jit__eval_shard` event of `XLA Modules`; of the
  name-stack paths an instruction has, those of that program decide its
  scope (the epoch program can hold the same instruction under another);
- the four phases of `BoundSync.evaluate` (PR 34): `trainer.evaluate
  .dispatch` (the program's call), `.wait` (the first `float()`: the
  program, and the pull that rides behind it), `.pull` (the second
  `float()`), `.reg` (the eager regulariser and its pull).
  `program_spans.read_events` keeps the names of its own list only, so the
  two new ones are read here.

Definitions, the same for every PR:

eval programs   events of `XLA Modules` named `jit__eval_shard`, first device
crumbs          every other program outside the epoch program: the fit
                loop's eager slices, converts, powers, sums, key folds
busy            `reduce_trace.account`'s: union of the operations' and the
                programs' intervals; an evaluation program's busy time that
                no operation accounts for is its loop control ("no operation")
host phase      of a device event: the span among `PHASE_OF` that holds the
                event's start on the trace's clock, else "none".  The
                profiler lays device and host events on one clock, to about
                a millisecond on the v5e: the line prints `clock_skew_us`,
                how far the device's clock lies behind the host's, bracketed
                by cause and effect (`_clock_skew`).  By that much a device
                event, and the end of a gap, can lie in the phase before its
                own: the phases' idle times SUM exactly, the split between
                two neighbours is good to the skew an evaluation
idle            `reduce_trace.gaps_of` on the worst device, a gap's part
                inside a phase (as `program_spans` splits it by span)

Identities, on the printed line: `eval_margins_ms + eval_rows_ms +
eval_other_ms + crumb_device_ms` against `eval_device_ms` (`device.identity
_rel`), and the four phases' idle against the idle inside `trainer.evaluate`
(`idle.unphased_ms`).

A trace without the new names (neither `dsgd.eval_rows` nor
`dsgd.eval_reduce` in the first device's metadata, or no
`trainer.evaluate.wait` / `.reg` span in the window: the commit before
them) reads None in every metric, and the line says `"named": false`.
Where the names are there and no operation ran under `dsgd.eval_rows`
(the compiler fused a chunk's fetch into what consumes it: dense rows, an
output axis) `eval_rows_ms` is 0.
"""

from __future__ import annotations

import bisect
import json
import re
import time
from typing import Dict, List, Optional

from benchmark import program_spans, reduce_trace

EVAL_PROGRAM = "jit__eval_shard"
EVAL_PATH = "jit(_eval_shard)"
MARGIN_SCOPES = ("dsgd.margins", "dsgd.onehot")
ROWS_SCOPE = "dsgd.eval_rows"
# the scopes PR 34 gave the evaluation programs: a trace that holds neither is older
NEW_SCOPES = (ROWS_SCOPE, "dsgd.eval_reduce")
DISPATCH, WAIT, PULL, REG = PHASES = (
    "trainer.evaluate.dispatch", "trainer.evaluate.wait",
    "trainer.evaluate.pull", "trainer.evaluate.reg")
# the spans a device event's start is looked up in: none of them holds another
PHASE_OF = PHASES + ("trainer.bookkeeping", "trainer.criterion", "trainer.epoch", "ckpt.save")
NO_PHASE = "none"
NO_OPERATION = "no operation"
DEVICE_METRICS = ("eval_margins_ms", "eval_rows_ms", "eval_other_ms", "crumb_device_ms",
                  "boundary_programs")
IDLE_METRICS = {"eval_pull_idle_ms": PULL, "eval_reg_idle_ms": REG}  # metric -> its phase
METRICS = DEVICE_METRICS + tuple(IDLE_METRICS)


def read_spans(path: str, names) -> list:
    """[(start, end, name, stats)] of the `/host:CPU` events named in `names`."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, dict(ev.stats))
                        for ev in line.events if ev.name in names]
    return out


def _program_name(module_event: str) -> str:
    return re.sub(r"\(.*\)$", "", module_event)


def _eval_scope(paths: Dict[str, List[str]], name: str) -> Optional[str]:
    """The scope of an operation of an evaluation program: by the paths
    that program gives the instruction, else by all of them."""
    own = [p for p in paths.get(name, ()) if p.startswith(EVAL_PATH)]
    if not own:
        return program_spans.scope_of_event(paths, name)
    scopes = {program_spans.scope_of(p) for p in own}
    return program_spans.AMBIGUOUS if len(scopes) > 1 else next(iter(scopes))


class _Phases:
    """Which of `PHASE_OF`'s spans holds a moment."""

    def __init__(self, spans):
        self.spans = sorted((s, e, n) for s, e, n, _stats in spans if n in PHASE_OF)
        self.starts = [s for s, _e, _n in self.spans]

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and t < self.spans[i][1]:
            return self.spans[i][2]
        return NO_PHASE


def _add(d: dict, key, value) -> None:
    d[key] = d.get(key, 0.0) + value


def _device_time(trace: dict, ops, modules, paths, phases: _Phases) -> dict:
    """The first device's busy milliseconds per epoch outside the epoch
    program: the evaluation programs by scope, the crumbs by host phase and
    by program, and every program run counted."""
    reduced = trace["devices"][trace["detail_device"]]
    runs = reduced["program"]["runs"]
    per_epoch = lambda ns: 1e-6 * ns / runs  # noqa: E731
    opens_in = trace["opens_in"]
    epoch_regions = sorted((s, e) for s, e, n in modules if opens_in in n)
    eval_regions = sorted((s, e) for s, e, n in modules if n.startswith(EVAL_PROGRAM))
    crumbs = [(s, e, n) for s, e, n in modules
              if opens_in not in n and not n.startswith(EVAL_PROGRAM)]
    _in_epoch, outside = reduce_trace.split_by_regions(ops, epoch_regions)
    in_eval, in_crumbs = reduce_trace.split_by_regions(outside, eval_regions)

    by_scope: Dict[str, float] = {}
    for name, ns in reduce_trace.self_times(in_eval).items():
        _add(by_scope, _eval_scope(paths, name) or program_spans.UNSCOPED, per_epoch(ns))
    eval_busy = per_epoch(reduce_trace.union_seconds(
        [(s, e) for s, e, _n in in_eval] + eval_regions))
    by_scope[NO_OPERATION] = max(eval_busy - sum(by_scope.values()), 0.0)
    margins = sum(by_scope.get(s, 0.0) for s in MARGIN_SCOPES)
    rows = by_scope.get(ROWS_SCOPE, 0.0)

    # a crumb is a program of one or two operations: its event is its busy time
    crumb_by_phase: Dict[str, float] = {}
    crumb_by_program: Dict[str, float] = {}
    for s, e, n in crumbs:
        _add(crumb_by_phase, phases.at(s), per_epoch(e - s))
        _add(crumb_by_program, _program_name(n), per_epoch(e - s))
    crumb_busy = per_epoch(reduce_trace.union_seconds(
        [(s, e) for s, e, _n in in_crumbs] + [(s, e) for s, e, _n in crumbs]))

    count_by_name: Dict[str, float] = {}
    count_by_phase: Dict[str, float] = {}
    for s, _e, n in modules:
        _add(count_by_name, _program_name(n), 1.0 / runs)
        if opens_in not in n:  # the epoch program's event opens the window, not a phase
            _add(count_by_phase, phases.at(s), 1.0 / runs)
    eval_device_ms = 1e3 * reduced["between"]["busy_s"] / runs
    total = eval_busy + crumb_busy
    return {
        "runs": runs, "eval_programs": len(eval_regions) / runs,
        "scoped": any(scope in path for known in paths.values() for path in known
                      for scope in NEW_SCOPES),
        "eval_ms_by_scope": dict(sorted(by_scope.items())),
        "eval_margins_ms": margins, "eval_rows_ms": rows,
        "eval_other_ms": eval_busy - margins - rows,
        "ambiguous_ms": by_scope.get(program_spans.AMBIGUOUS, 0.0),
        "crumb_device_ms": crumb_busy,
        "crumb_ms_by_phase": dict(sorted(crumb_by_phase.items())),
        "crumb_ms_by_program": dict(sorted(crumb_by_program.items())),
        "boundary_programs": len(modules) / runs,
        "programs_by_name": dict(sorted(count_by_name.items())),
        "programs_by_phase": dict(sorted(count_by_phase.items())),
        "sum_ms": total, "eval_device_ms": eval_device_ms,
        "identity_rel": (total - eval_device_ms) / eval_device_ms if eval_device_ms else 0.0}


def _idle_time(runs: int, busy, spans, lo: float, hi: float) -> dict:
    """The worst device's idle milliseconds per epoch inside each phase of
    `BoundSync.evaluate`, beside the whole inside `trainer.evaluate`."""
    gaps = reduce_trace.gaps_of(busy, lo, hi)
    cover = lambda name: program_spans.merged(  # noqa: E731
        (max(s, lo), min(e, hi)) for s, e, n, _stats in spans
        if n == name and min(e, hi) > max(s, lo))
    per_epoch = lambda ns: 1e-6 * ns / runs  # noqa: E731
    by_phase = {name: per_epoch(program_spans.overlap(gaps, cover(name))) for name in PHASES}
    evaluate = per_epoch(program_spans.overlap(gaps, cover("trainer.evaluate")))
    return {"runs": runs, "ms_by_phase": by_phase, "evaluate_ms": evaluate,
            "unphased_ms": evaluate - sum(by_phase.values())}


def _clock_skew(modules, whole: Dict[str, list]) -> Optional[dict]:
    """How far the device's clock lies behind the host's in the trace,
    bracketed by cause and effect, in microseconds: an evaluation program
    cannot start before the `.dispatch` span that launched it (`at_least`:
    the most any device event leads its dispatch), and cannot end after the
    `.wait` span that waited for it (`at_most`: the least any `.wait` outlasts
    its program; None on a trace without `.wait`).  The k-th whole span is
    paired with the k-th program, from the window's end."""
    programs = sorted((s, e) for s, e, n in modules if n.startswith(EVAL_PROGRAM))
    from_the_end = lambda name: zip(  # noqa: E731
        reversed(sorted(whole.get(name, ()))), reversed(programs))
    leads = [1e-3 * (span[0] - start) for span, (start, _end) in from_the_end(DISPATCH)]
    if not leads:
        return None
    lags = [1e-3 * (span[1] - end) for span, (_start, end) in from_the_end(WAIT)]
    return {"pairs": len(leads), "at_least": max(leads), "at_most": min(lags) if lags else None}


def attribute(trace: dict, devices: dict, spans: list, marks_end: Optional[float],
              paths_by_plane: Dict[str, Dict[str, List[str]]]) -> dict:
    """Everything of the `boundary_spans:` line, from the reduced `trace`
    and what `program_spans.read_events` / `read_paths` and `read_spans`
    returned.  `metrics` holds the seven readers' values, None each where
    the trace lacks the names."""
    hi = marks_end if marks_end is not None else max(
        e for lines in devices.values() for line in lines.values() for _s, e, _n in line)
    lo = hi - trace["window_s"] * 1e9

    def device(name: str):
        index = int(name.split(":")[1])
        lines = devices[index]
        clipped = lambda events: [  # noqa: E731
            (max(s, lo), min(e, hi), n) for s, e, n in events if min(e, hi) > max(s, lo)]
        return (clipped(lines.get(reduce_trace.OPS_LINE, [])),
                clipped(lines.get(reduce_trace.MODULES_LINE, [])),
                paths_by_plane.get(f"/device:TPU:{index}", {}))

    whole: Dict[str, list] = {}
    for span in spans:
        if lo <= span[0] and span[1] <= hi:
            whole.setdefault(span[2], []).append(span)
    phases = _Phases(spans)
    ops, modules, paths = device(trace["detail_device"])
    on_device = _device_time(trace, ops, modules, paths, phases)
    w_ops, w_modules, _paths = device(trace["worst_device"])
    runs = trace["devices"][trace["worst_device"]]["program"]["runs"]
    idle = _idle_time(runs, [(s, e) for s, e, _n in w_ops + w_modules], spans, lo, hi)
    counted = {name: len(whole.get(name, ())) for name in PHASES}
    named = bool(on_device["scoped"] and counted[WAIT] and counted[REG])
    metrics = dict.fromkeys(METRICS)
    if named:
        metrics.update({k: on_device[k] for k in DEVICE_METRICS})
        metrics.update({k: idle["ms_by_phase"][phase] for k, phase in IDLE_METRICS.items()})
    return {
        "named": named, "metrics": metrics, "device": on_device, "idle": idle,
        "phase_spans": counted,
        "host_ms_by_phase": {name: 1e-6 * sum(e - s for s, e, _n, _st in whole.get(name, ()))
                             / on_device["runs"] for name in PHASES},
        "clock_skew_us": _clock_skew(modules, whole)}


def parse(run) -> dict:
    """`attribute` of the run's trace file, with what reading it cost."""
    t0 = time.perf_counter()
    paths_by_plane = program_spans.read_paths(run.trace_path)
    devices, spans, marks_end = program_spans.read_events(run.trace_path)
    unread = set(PHASES) - set(program_spans.SPAN_NAMES)
    if unread:
        spans = spans + read_spans(run.trace_path, unread)
    out = attribute(run.trace, devices, spans, marks_end, paths_by_plane)
    out["parse_s"] = time.perf_counter() - t0
    return out


def of(run) -> Optional[dict]:
    """`parse(run)`, once per run; None without a reduced trace of a sync
    fit (no epoch program in it), and where the trace cannot be read
    (printed, never raised: the run of a commit that lacks the names, or
    whose trace reads otherwise, must not fail on these readers)."""
    trace = getattr(run, "trace", None)
    if trace is None or not getattr(run, "trace_path", None) or not trace.get("opens_in"):
        return None
    if not hasattr(run, "boundary_spans"):
        try:
            run.boundary_spans = parse(run)
            shown = run.boundary_spans
        except Exception as e:  # noqa: BLE001 - see docstring
            run.boundary_spans = None
            shown = {"error": f"{type(e).__name__}: {e}"}
        print(f"boundary_spans: {json.dumps(shown, default=float)}", flush=True)
    return run.boundary_spans


def metric(run, name: str) -> Optional[float]:
    """The value of one of `METRICS`, or None."""
    found = of(run)
    return found and found["metrics"][name]
