"""The program's own names in a profiler trace: which `jax.named_scope`
each device operation ran under, and where the program's `measure.span`s
lie on the profiler's clock.  The one helper the by-name per-layer metrics
share; parsed once per run, cached on the run, printed as one
`program_spans:` line before the result.

What the trace holds, beyond what `reduce_trace` reads (looked at by hand
on the v5e, PR 24):

- every `XLA Ops` event's *metadata* (`XPlane.event_metadata[id].stats`)
  carries `tf_op`, the JAX name stack of the instruction
  (`jit(_epoch_shard)/while/body/closed_call/dsgd.margins/dot_general:`),
  which is where `jax.named_scope` lands; a fusion has the path of its
  root.  `jax.profiler.ProfileData` (jax 0.9.0) exposes an event's own
  stats only, so the metadata is read here from the protobuf wire format:
  XSpace.planes=1; XPlane.name=2, .event_metadata=4, .stat_metadata=5 (maps:
  key=1, value=2); XEventMetadata.name=2, .stats=5; XStat.metadata_id=1,
  .str_value=5, .ref_value=7 (the id of a stat metadata whose name is the
  string); XStatMetadata.name=2.  Events still come from `ProfileData`,
  joined on the event name (= the metadata's name, the HLO instruction).
  Two programs can hold the same instruction under two paths: where such
  twins disagree on their scope their time is `ambiguous`, and printed.
- a `measure.span` of the program is an event named as the span on the
  line of the thread that opened it, on the plane `/host:CPU`, with the
  span's arguments (`epoch`, `split`, `worker`, `dispatch`) as its stats.

Definitions, the same for every PR:

window     the window of `reduce_trace`: it closes where the last `bench.*`
           annotation ends (without one, with the last device event) and is
           `run.trace["window_s"]` long; that is `first device event +
           cut_s` wherever the window opens inside a program
scope      the innermost `dsgd.*` component of an operation's path
steps,     taken from `run.trace["devices"][...]["program"]`, never
runs       recounted: a per-step number here divides by what
           `matmul_us_per_step` divides by
self time  `reduce_trace.self_times`, over the operations that start inside
           an event of the named program (`reduce_trace.split_by_regions`)
unscoped   the program's busy time under no `dsgd.*` scope: operations
           without one, and program time no operation accounts for (loop
           control), so that the scopes and `unscoped` sum to the program's
           busy seconds
gaps       `reduce_trace.gaps_of` over the worst device's operations and
           programs: time inside a program's event is busy, so every gap
           lies outside the named program; a gap's part inside a span is
           that span's idle time
"""

from __future__ import annotations

import json
import os
import re
import time
from typing import Dict, Iterable, List, Optional, Tuple

from benchmark import reduce_trace

# The spans the readers look for.  A literal, not imported from the
# program: the yardstick must not move when the program does.
SPAN_NAMES = (
    "trainer.epoch", "trainer.evaluate", "trainer.evaluate.dispatch",
    "trainer.evaluate.pull", "trainer.bookkeeping", "trainer.criterion",
    "ckpt.save",
    "slave.async.iteration", "slave.async.drain", "slave.async.step",
    "slave.async.apply", "slave.async.pull", "slave.async.push",
    "master.async.check",
)
# the spans of `SyncTrainer.fit` that do not lie inside another one
LOOP_SPANS = ("trainer.epoch", "trainer.evaluate", "trainer.bookkeeping",
              "trainer.criterion", "ckpt.save")
# the scopes of one SGD step (draw ... update); layout and eval are not
STEP_SCOPES = ("dsgd.draw", "dsgd.onehot", "dsgd.margins", "dsgd.coeff",
               "dsgd.scatter", "dsgd.regularize", "dsgd.allreduce", "dsgd.update")
KSTEP_PROGRAM = "jit_kstep"
AMBIGUOUS = "ambiguous"
UNSCOPED = "unscoped"

_SCOPE = re.compile(r"dsgd\.[a-z_]+")

Interval = Tuple[float, float]


# -- the protobuf wire format ------------------------------------------------------


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf, start: int, end: int):
    """(field number, wire type, value) of one message: an int for a
    varint, (start, end) for a length-delimited field; fixed-width fields
    are skipped over."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, wire, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, wire, (i, i + size)
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an XSpace")


def _text(buf, span: Interval) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    """(key, value span) of one entry of a protobuf map field."""
    key = value = None
    for number, wire, v in _fields(buf, *span):
        if number == 1 and wire == 0:
            key = v
        elif number == 2 and wire == 2:
            value = v
    return key, value


def read_paths(path: str) -> Dict[str, Dict[str, List[str]]]:
    """{plane name: {event name: [tf_op, ...]}} of every `/device:` plane
    of the `.xplane.pb` at `path`: the distinct name-stack paths of the
    event metadata of that name (more than one where several programs hold
    the instruction).  Reads the metadata tables only: linear in the file."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: Dict[str, Dict[str, List[str]]] = {}
    for number, wire, plane in _fields(buf, 0, len(buf)):
        if number != 1 or wire != 2:
            continue
        name, event_spans, stat_names = "", [], {}
        for n, w, v in _fields(buf, *plane):
            if n == 2 and w == 2:
                name = _text(buf, v)
            elif n == 4 and w == 2:
                event_spans.append(v)
            elif n == 5 and w == 2:
                key, value = _map_entry(buf, v)
                if value is not None:
                    for n2, w2, v2 in _fields(buf, *value):
                        if n2 == 2 and w2 == 2:
                            stat_names[key] = _text(buf, v2)
        if not name.startswith("/device:"):
            continue
        tf_op_ids = {k for k, v in stat_names.items() if v == "tf_op"}
        paths: Dict[str, List[str]] = {}
        for span in event_spans:
            _key, meta = _map_entry(buf, span)
            if meta is None:
                continue
            event_name, tf_op = "", None
            for n, w, v in _fields(buf, *meta):
                if n == 2 and w == 2:
                    event_name = _text(buf, v)
                elif n == 5 and w == 2:
                    stat_id = value = None
                    for n2, w2, v2 in _fields(buf, *v):
                        if n2 == 1 and w2 == 0:
                            stat_id = v2
                        elif n2 == 5 and w2 == 2:
                            value = _text(buf, v2)
                        elif n2 == 7 and w2 == 0:
                            value = stat_names.get(v2, "")
                    if stat_id in tf_op_ids and value is not None:
                        tf_op = value
            if tf_op is not None:
                known = paths.setdefault(event_name, [])
                if tf_op not in known:
                    known.append(tf_op)
        out[name] = paths
    return out


def scope_of(tf_op: Optional[str]) -> Optional[str]:
    """The innermost `dsgd.*` component of a name-stack path, or None."""
    found = _SCOPE.findall(tf_op or "")
    return found[-1] if found else None


def scope_of_event(paths: Dict[str, List[str]], name: str) -> Optional[str]:
    """The scope of the event `name`; AMBIGUOUS where its twins disagree."""
    scopes = {scope_of(p) for p in paths.get(name, ())}
    if len(scopes) > 1:
        return AMBIGUOUS
    return next(iter(scopes), None)


# -- intervals ----------------------------------------------------------------------


def merged(intervals: Iterable[Interval]) -> List[Interval]:
    """The union of intervals as sorted, disjoint intervals."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Total length of the intersection of two sorted, disjoint lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


# -- reading the trace ------------------------------------------------------------------


def read_events(path: str):
    """(devices, spans, marks_end) in nanoseconds.  devices as
    `reduce_trace.read_planes` gives them; spans: [(start, end, name, stats)]
    of the program's spans on every line of `/host:CPU`; marks_end: where
    the last `bench.*` annotation ends, or None."""
    from jax.profiler import ProfileData

    wanted = frozenset(SPAN_NAMES)
    devices, spans, marks_end = {}, [], None
    for plane in ProfileData.from_file(path).planes:
        m = reduce_trace.DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = {
                line.name: [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                            for ev in line.events]
                for line in plane.lines
                if line.name in (reduce_trace.MODULES_LINE, reduce_trace.OPS_LINE)}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name in wanted:
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, name,
                                      dict(ev.stats)))
                    elif name.startswith(reduce_trace.ANNOTATION_PREFIX):
                        end = ev.start_ns + ev.duration_ns
                        marks_end = end if marks_end is None else max(marks_end, end)
    return devices, spans, marks_end


def scope_seconds(ops, regions, paths) -> Dict[tuple, float]:
    """Self seconds per (scope, class) of the operations that start inside
    one of the sorted, disjoint `regions`: scope None is no scope, the
    class is `reduce_trace.op_class`'s, so that a scope's time can be laid
    against the class metrics that count the same operations by opcode."""
    inside, _outside = reduce_trace.split_by_regions(ops, regions)
    out: Dict[tuple, float] = {}
    for name, ns in reduce_trace.self_times(inside).items():
        key = (scope_of_event(paths, name), reduce_trace.op_class(name))
        out[key] = out.get(key, 0.0) + ns * 1e-9
    return out


def _by_scope(by_scope_and_class: Dict[tuple, float]) -> Dict[Optional[str], float]:
    out: Dict[Optional[str], float] = {}
    for (scope, _cls), seconds in by_scope_and_class.items():
        out[scope] = out.get(scope, 0.0) + seconds
    return out


def _epoch_program(program: dict, opens_in: str, ops, modules, paths) -> dict:
    """The named program's busy time by scope, per step."""
    regions = sorted((s, e) for s, e, n in modules if opens_in in n)
    by_both = scope_seconds(ops, regions, paths)
    named = {k: v for k, v in _by_scope(by_both).items() if k is not None}
    # the scopes and `unscoped` sum to the program's busy seconds
    named[UNSCOPED] = max(program["busy_s"] - sum(named.values()), 0.0)
    # program time between operations (a running program's loop control)
    by_both[(None, "no operation")] = named[UNSCOPED] - sum(
        v for (scope, _cls), v in by_both.items() if scope is None)
    steps = program["step"]["steps"]
    return {
        "steps": steps, "busy_s": program["busy_s"],
        "busy_us_per_step": 1e6 * program["busy_s"] / steps,
        "step_us": 1e6 * program["step"]["seconds"],
        "scoped": any(k.startswith("dsgd.") for k in named),
        "us_per_step": {k: 1e6 * v / steps for k, v in sorted(named.items())},
        # the same self times by `reduce_trace`'s classes: what of a scope
        # `matmul_us_per_step` and `allreduce_us_per_step` count
        "class_us_per_step": {
            f"{scope or UNSCOPED}/{cls}": 1e6 * v / steps
            for (scope, cls), v in sorted(by_both.items(), key=str) if v > 0}}


def _kstep_program(runs: int, seconds: float, ops, modules, paths) -> dict:
    """Hogwild's k-step program by scope, per run."""
    regions = sorted((s, e) for s, e, n in modules if n.startswith(KSTEP_PROGRAM))
    by_scope = _by_scope(scope_seconds(ops, regions, paths))
    entry = sum(v for k, v in by_scope.items() if k not in STEP_SCOPES)
    return {
        "runs": runs, "seconds": seconds,
        "scoped": any(k in STEP_SCOPES for k in by_scope),
        "entry_us_per_run": 1e6 * entry / runs,
        "us_per_run": {str(k): 1e6 * v / runs
                       for k, v in sorted(by_scope.items(), key=lambda kv: str(kv[0]))}}


def _loop_idle(runs: int, busy: List[Interval], spans: list, whole: Dict[str, list],
               lo: float, hi: float) -> dict:
    """The worst device's idle time per epoch, by the span that covers it."""
    gaps = reduce_trace.gaps_of(busy, lo, hi)
    # a span that began before the window still names the gaps it covers
    cover = lambda names: merged(  # noqa: E731
        (max(s[0], lo), min(s[1], hi)) for s in spans
        if s[2] in names and min(s[1], hi) > max(s[0], lo))
    total = sum(e - s for s, e in gaps)
    per_span = {name: overlap(gaps, cover((name,))) for name in SPAN_NAMES
                if name.startswith(("trainer.", "ckpt."))}
    per_span["no span"] = total - overlap(gaps, cover(LOOP_SPANS))
    return {"runs": runs, "gaps": len(gaps),
            "evaluate_spans": len(whole.get("trainer.evaluate", ())),
            "total_ms": 1e-6 * total / runs,
            "ms_per_epoch": {k: 1e-6 * v / runs for k, v in per_span.items()}}


def _async_dispatches(whole: Dict[str, list]) -> dict:
    """Mean microseconds of a Hogwild iteration and of each of its phases
    (joined on `worker` and `dispatch`), over the whole iterations."""
    iterations = whole["slave.async.iteration"]
    key = lambda s: (s[3].get("worker"), s[3].get("dispatch"))  # noqa: E731
    mean_us = lambda ns: 1e-3 * sum(ns) / len(iterations)  # noqa: E731
    phase_us = {}
    for name in SPAN_NAMES:
        if name.startswith("slave.async.") and name != "slave.async.iteration":
            took = {key(s): s[1] - s[0] for s in whole.get(name, ())}
            phase_us[name] = mean_us(took.get(key(s), 0.0) for s in iterations)
    checks = whole.get("master.async.check", ())
    return {"iterations": len(iterations),
            "workers": len({s[3].get("worker") for s in iterations}),
            "iteration_us": mean_us(s[1] - s[0] for s in iterations),
            "phase_us": phase_us,
            "checks": len(checks), "check_ms": 1e-6 * sum(s[1] - s[0] for s in checks)}


def _clipped(events, lo: float, hi: float):
    return [(max(s, lo), min(e, hi), n) for s, e, n in events if min(e, hi) > max(s, lo)]


def attribute(trace: dict, devices: dict, spans: list, marks_end: Optional[float],
              paths_by_plane: Dict[str, Dict[str, List[str]]]) -> dict:
    """What the readers need, from the reduced `trace` of `reduce_trace`
    and what `read_events` and `read_paths` returned (seconds, and the
    units the keys name)."""
    hi = marks_end if marks_end is not None else max(
        e for lines in devices.values() for line in lines.values() for _s, e, _n in line)
    lo = hi - trace["window_s"] * 1e9

    def device(name: str):
        index = int(name.split(":")[1])
        lines = devices[index]
        return (_clipped(lines.get(reduce_trace.OPS_LINE, []), lo, hi),
                _clipped(lines.get(reduce_trace.MODULES_LINE, []), lo, hi),
                paths_by_plane.get(f"/device:TPU:{index}", {}))

    out = {"program": None, "kstep": None, "idle": None, "async": None}
    reduced = trace["devices"][trace["detail_device"]]
    detail = device(trace["detail_device"])
    program, opens_in = reduced.get("program"), trace.get("opens_in")
    if opens_in and program and program.get("step"):
        out["program"] = _epoch_program(program, opens_in, *detail)
    kstep = reduced["modules"].get(KSTEP_PROGRAM)
    if kstep and kstep[0]:
        out["kstep"] = _kstep_program(kstep[0], kstep[1], *detail)
    whole: Dict[str, list] = {}  # the spans that lie whole inside the window
    for span in spans:
        if lo <= span[0] and span[1] <= hi:
            whole.setdefault(span[2], []).append(span)
    worst_program = trace["devices"][trace["worst_device"]].get("program")
    if worst_program and worst_program.get("runs"):
        w_ops, w_modules, _paths = device(trace["worst_device"])
        out["idle"] = _loop_idle(worst_program["runs"],
                                 [(s, e) for s, e, _n in w_ops + w_modules],
                                 spans, whole, lo, hi)
    if whole.get("slave.async.iteration"):
        out["async"] = _async_dispatches(whole)
    out["ambiguous_us_per_step"] = (out["program"] or {}).get(
        "us_per_step", {}).get(AMBIGUOUS, 0.0)
    out["spans_in_window"] = {k: len(v) for k, v in sorted(whole.items())}
    return out


def parse(run) -> dict:
    """`attribute` of the run's trace file, with what reading it cost."""
    t0 = time.perf_counter()
    paths_by_plane = read_paths(run.trace_path)
    t_paths = time.perf_counter()
    devices, spans, marks_end = read_events(run.trace_path)
    out = attribute(run.trace, devices, spans, marks_end, paths_by_plane)
    out.update(xplane_bytes=os.path.getsize(run.trace_path),
               read_paths_s=t_paths - t0, parse_s=time.perf_counter() - t0)
    return out


def of(run) -> Optional[dict]:
    """`parse(run)`, once per run; None without a reduced trace, and where
    the trace cannot be read (printed, never raised: a reader added by a
    later PR must not fail the run of the commit before it)."""
    if getattr(run, "trace", None) is None or not getattr(run, "trace_path", None):
        return None
    if not hasattr(run, "program_spans"):
        try:
            run.program_spans = parse(run)
            shown = run.program_spans
        except Exception as e:  # noqa: BLE001 - see docstring
            run.program_spans = None
            shown = {"error": f"{type(e).__name__}: {e}"}
        print(f"program_spans: {json.dumps(shown, default=float)}", flush=True)
    return run.program_spans


def part(run, key: str) -> Optional[dict]:
    """One part of `of(run)` ("program", "kstep", "idle", "async"), or None."""
    found = of(run)
    return found and found[key]


def scope_us_per_step(run, scopes: Tuple[str, ...]) -> Optional[float]:
    """Microseconds of self time per step under `scopes` inside the named
    program, detail device; None where the trace holds no `dsgd.*` scope
    (a commit before the scopes) or no steps."""
    program = part(run, "program")
    if not program or not program["scoped"]:
        return None
    return sum(program["us_per_step"].get(s, 0.0) for s in scopes)
