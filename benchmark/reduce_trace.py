"""From a profiler trace (`.xplane.pb`) to the numbers the per-layer
metrics read, with `jax.profiler.ProfileData` and nothing else.

What a TPU trace of this program holds (looked at by hand on the v5e,
PR 22; PERF.md section 5 has the walk-through):

- one plane per chip, `/device:TPU:<n>`.  Its line `XLA Modules` has one
  event per execution of a compiled program, named `jit_<function>(<id>)`:
  `jit__epoch_shard` (the compiled epoch), `jit__eval_shard` (one per
  split), `jit_kstep` (Hogwild's local steps), and the fit loop's eager
  crumbs (`jit_convert_element_type`, `jit_dynamic_slice`,
  `jit_integer_pow`, `jit__reduce_sum`, `jit__threefry_fold_in`: seventeen
  programs an epoch).  Its line `XLA Ops` has one event per HLO operation,
  named by the whole HLO instruction (`%fusion.61 = f32[4,7600]{...}
  fusion(...), kind=kOutput, calls=...`), nested: a `%while` spans its loop
  and the body's operations lie inside it.  `Async XLA Ops` holds the
  copy-start/copy-done pairs and is not read.
- the host is the plane `/host:CPU`; the line `python` carries the python
  frames (`$file.py:line function`) and the benchmark's own `bench.*`
  annotations.

Definitions, the same for every PR:

window      from the first device event (or the first `bench.*` annotation
            if earlier) to the end of the last `bench.*` annotation (the
            drivers close one at the boundary where they stop the
            profiler); without annotations, to the last device event.
            Where the caller names a program (`opens_in`: the compiled
            epoch), the window opens with the first event of that program
            on the line `XLA Modules` (a program already running when the
            profiler began is recorded from the profiler's start) and what
            the trace holds before it is cut off.  The profiler comes up
            on one chip after the other (10 ms apart on the v5e), so the
            window opens where the LAST device's first such event starts:
            from there on every device is recorded.  Every device has to
            be inside that program at that moment; one that is not, or
            that ran no such program before the window closed, is an error
program     the part of the window inside events of the named program, and
/ between   the rest.  Each has its seconds, busy and idle seconds and the
            classes' self times.  Per-step numbers come from `program`
            alone, evaluation and the loop's idle time from `between`
            alone, so neither moves with where the window happened to open
busy        union of the intervals of a device's `XLA Ops` and `XLA Modules`
            events, clipped to the window (a parent and its children count
            once; between two operations of one program the device runs
            that program's loop control)
idle share  1 - busy / window; the run reports the worst device's
self time   an operation's duration minus that of the events nested in it:
            what `device_ops` ranks and what the classes sum.  The own time
            of containers (while, conditional, call: loop control) is the
            class `container` and is not ranked.
classes     by opcode and fusion kind.  matmul: convolution, dot, and
            `kind=kOutput` fusions (on the TPU an output fusion is rooted
            at a convolution: the one-hot gather and scatter of
            ops/mxu.py).  allreduce: all-reduce[-start|-done].  collective:
            the other collectives.  gather: `kind=kCustom` fusions and
            gather / dynamic-slice (the row draws from the resident data).
            copy: copy, copy-start/-done, transpose.  other: the rest.
step        inside the named program, the operation that occurs most often
            occurs once a step: its count is the number of steps the
            window holds, the median distance between its starts is the
            device time of one step
gap         a maximal interval of the window in which no operation ran on
            the worst device; the longest ones are named after the
            `bench.*` annotation they fall in and the innermost python
            frame that covers them whole
"""

from __future__ import annotations

import bisect
import re
import statistics
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "bench."
CONTAINERS = ("while", "conditional", "call", "async-start", "async-done")


class TraceError(Exception):
    """The trace cannot give the numbers: no device plane, no operations."""


_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")
_KIND = re.compile(r"kind=k(\w+)")
_RESULT = re.compile(r"^\(?([a-z0-9]+\[[0-9,]*\])")


def parse_op(text: str) -> Tuple[str, str, str, str]:
    """(name, opcode, fusion kind, result type) of an `XLA Ops` event name,
    which is the HLO instruction: `%name = type opcode(operands), attrs`."""
    name, _, rhs = text.partition(" = ")
    name = name.strip().lstrip("%")
    if not rhs:
        return name, re.sub(r"[.\d]+$", "", name), "", ""
    opcode = _OPCODE.search(" " + rhs)
    kind = _KIND.search(rhs)
    result = _RESULT.match(rhs)
    return (name, opcode.group(1) if opcode else "", kind.group(1) if kind else "",
            result.group(1) if result else "")


def op_class(text: str) -> str:
    """The class of an HLO operation, from the instruction the trace prints."""
    _name, opcode, kind, _result = parse_op(text)
    if opcode in CONTAINERS:
        return "container"
    if opcode.startswith("all-reduce"):
        return "allreduce"
    if opcode.startswith(("all-gather", "all-to-all", "collective-permute",
                          "reduce-scatter", "collective-broadcast")):
        return "collective"
    if opcode in ("convolution", "dot") or (opcode == "fusion" and kind == "Output"):
        return "matmul"
    if (opcode == "fusion" and kind == "Custom") or opcode in ("gather", "dynamic-slice"):
        return "gather"
    if opcode in ("copy", "copy-start", "copy-done", "transpose"):
        return "copy"
    return "other"


def op_label(text: str) -> str:
    """A short, stable label for the ledger: name, result type, kind."""
    name, opcode, kind, result = parse_op(text)
    what = f"k{kind}" if kind else opcode
    return " ".join(x for x in (name, result, what) if x)[:80]


def union_seconds(intervals: List[Tuple[float, float]]) -> float:
    """Total length of the union of [start, end) intervals (any unit)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_of(intervals: List[Tuple[float, float]], lo: float, hi: float):
    """The maximal sub-intervals of [lo, hi) that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Per name, duration minus the time of events nested inside (one
    line's events nest properly: a child lies within its parent)."""
    out: Dict[str, float] = {}
    stack: List[list] = []  # [end, name, self]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            _end, name, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for s, e, name in sorted(events, key=lambda t: (t[0], -t[1])):
        close(s)
        if stack:
            stack[-1][2] -= (e - s)
        stack.append([e, name, e - s])
    close(float("inf"))
    return out


def _clip(s: float, e: float, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def read_planes(path: str):
    """(devices, host) in nanoseconds.  devices: {index: {line name:
    [(start, end, name)]}} for the `XLA Modules` and `XLA Ops` lines of
    every device plane.  host: [(start, end, name)] of the host lines that
    carry a `bench.*` annotation (the python frames are on them)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {}
            for line in plane.lines:
                if line.name in (MODULES_LINE, OPS_LINE):
                    lines[line.name] = [
                        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in line.events]
            devices[int(m.group(1))] = lines
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                events = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                          for ev in line.events]
                if any(n.startswith(ANNOTATION_PREFIX) for _s, _e, n in events):
                    host.extend(events)
    return devices, host


def covering_frame(frames, s: float, e: float) -> Optional[str]:
    """The shortest python frame of a source file that covers [s, e] whole."""
    best = None
    for fs, fe, n in frames:
        if fs <= s and e <= fe and ".py:" in n and (best is None or fe - fs < best[0]):
            best = (fe - fs, n)
    return best[1].lstrip("$") if best else None


def split_by_regions(ops, regions):
    """(inside, outside): the operations that start inside one of the sorted,
    disjoint `regions`, and the others."""
    starts = [r[0] for r in regions]
    inside, outside = [], []
    for op in ops:
        i = bisect.bisect_right(starts, op[0]) - 1
        (inside if i >= 0 and op[0] < regions[i][1] else outside).append(op)
    return inside, outside


def account(ops, programs, seconds: float) -> dict:
    """Busy and idle seconds of `seconds` of the window in which `ops` ran
    inside the `programs` intervals, with the self time of every operation
    (`own`, by instruction) and of every class (nanosecond events, second
    results).  Program time no operation accounts for is loop control: a
    program that was running when the profiler began has no `%while` event
    of its own, only its body's operations."""
    busy = union_seconds([(s, e) for s, e, _n in ops] + list(programs)) * 1e-9
    own = {text: ns * 1e-9 for text, ns in self_times(ops).items()}
    classes: Dict[str, float] = {}
    for text, seconds_own in own.items():
        cls = op_class(text)
        classes[cls] = classes.get(cls, 0.0) + seconds_own
    uncounted = busy - sum(classes.values())
    if uncounted > 0:
        classes["container"] = classes.get("container", 0.0) + uncounted
    return {"seconds": seconds, "busy_s": busy, "idle_s": max(seconds - busy, 0.0),
            "classes": classes, "own": own}


def steps_of(ops) -> Optional[dict]:
    """How many steps `ops` (the operations inside the named program) hold,
    and the device time of one (see the module's definitions)."""
    starts: Dict[str, List[float]] = {}
    for s, _e, n in ops:
        if op_class(n) != "container":
            starts.setdefault(n, []).append(s)
    if not starts:
        return None
    most = max(starts.values(), key=len)
    if len(most) < 8:
        return None
    most.sort()
    return {"seconds": statistics.median(b - a for a, b in zip(most, most[1:])) * 1e-9,
            "steps": len(most)}


def class_us_per_step(device: dict, cls: str, absent=None) -> Optional[float]:
    """Microseconds of self time per step that the class `cls` takes inside
    the named program on one reduced device (an entry of `devices`);
    `absent` where the program ran no operation of the class."""
    program = device.get("program")
    if not program or not program.get("step"):
        return None
    if cls not in program["classes"]:
        return absent
    return 1e6 * program["classes"][cls] / program["step"]["steps"]


def reduce(path: str, opens_in: Optional[str] = None, top: int = 10,
           named_gaps: int = 40) -> dict:
    """The reduced trace of the file at `path` (seconds throughout)."""
    devices, host = read_planes(path)
    if not devices:
        raise TraceError(f"{path}: no /device:TPU:<n> plane in the trace")
    return reduce_events(devices, host, opens_in, top, named_gaps)


def _merged(a: Dict[str, float], b: Dict[str, float]) -> Dict[str, float]:
    out = dict(a)
    for k, v in b.items():
        out[k] = out.get(k, 0.0) + v
    return out


def reduce_events(devices: dict, host: list, opens_in: Optional[str] = None,
                  top: int = 10, named_gaps: int = 40) -> dict:
    """The reduced trace of what `read_planes` returned."""
    marks = sorted((s, e, n) for s, e, n in host if n.startswith(ANNOTATION_PREFIX))
    frames = [(s, e, n) for s, e, n in host if n.startswith("$")]
    spans = [(s, e) for lines in devices.values() for line in lines.values()
             for s, e, _n in line]
    if not spans:
        raise TraceError("nothing ran on a device in the trace")
    first = min(s for s, _e in spans)
    hi = max(e for _s, e, _n in marks) if marks else max(e for _s, e in spans)
    if opens_in is None:
        lo = min(first, marks[0][0] if marks else float("inf"))
    else:
        opened = []
        for index, lines in sorted(devices.items()):
            runs = [s for s, _e, n in lines.get(MODULES_LINE, []) if opens_in in n and s < hi]
            if not runs:
                raise TraceError(
                    f"device {index} ran no program named *{opens_in}* before the "
                    "window closed: the trace does not open inside it")
            opened.append(min(runs))
        lo = max(opened)
    if hi <= lo:
        raise TraceError("the traced window is empty")
    window = (hi - lo) * 1e-9

    per_device, spans_of = {}, {}
    for index, lines in sorted(devices.items()):
        ops = [c + (n,) for s, e, n in lines.get(OPS_LINE, []) if (c := _clip(s, e, lo, hi))]
        if not ops:
            raise TraceError(f"no operation ran on device {index} in the window")
        modules = [c + (n,) for s, e, n in lines.get(MODULES_LINE, [])
                   if (c := _clip(s, e, lo, hi))]
        if opens_in is not None:
            s_first, _e, n_first = min(modules, default=(hi, hi, ""))
            if opens_in not in n_first or s_first > lo:
                raise TraceError(
                    f"device {index} is not inside a program named *{opens_in}* where "
                    f"the window opens (it runs {n_first or 'nothing'!r} first)")
        by_module: Dict[str, list] = {}
        for s, e, n in modules:
            entry = by_module.setdefault(re.sub(r"\(.*\)$", "", n), [0, 0.0])
            entry[0] += 1
            entry[1] += (e - s) * 1e-9
        program = None
        if opens_in is not None:
            regions = sorted((s, e) for s, e, n in modules if opens_in in n)
            others = [(s, e) for s, e, n in modules if opens_in not in n]
            inside, outside = split_by_regions(ops, regions)
            in_program = sum(e - s for s, e in regions) * 1e-9
            program = account(inside, regions, in_program)
            program.update(runs=len(regions), step=steps_of(inside))
            between = account(outside, others, window - in_program)
        else:
            between = account(ops, [(s, e) for s, e, _n in modules], window)
        own = _merged(program["own"], between["own"]) if program else between["own"]
        kinds: Dict[str, float] = {}
        for text, seconds in own.items():
            if op_class(text) != "container":  # a loop's own time is a class, not an operation to rank
                label = op_label(text)
                kinds[label] = kinds.get(label, 0.0) + seconds
        name = f"TPU:{index}"
        spans_of[name] = [(s, e) for s, e, _n in ops + modules]
        busy = union_seconds(spans_of[name]) * 1e-9
        strip = lambda d: {k: v for k, v in d.items() if k != "own"}  # noqa: E731
        per_device[name] = {
            "busy_s": busy, "idle_share": 1.0 - busy / window,
            "classes": _merged(program["classes"], between["classes"]) if program
            else between["classes"],
            "ops": kinds, "modules": by_module,
            "program": strip(program) if program else None,
            "between": strip(between) if program else None}

    names = sorted(per_device, key=lambda k: int(k.split(":")[1]))
    worst = max(names, key=lambda k: per_device[k]["idle_share"])
    gaps = sorted(gaps_of(spans_of[worst], lo, hi), key=lambda g: g[0] - g[1])
    by_label: Dict[str, float] = {}
    for n_gap, (s, e) in enumerate(gaps):
        label = "shorter gaps"
        if n_gap < named_gaps:
            mid = 0.5 * (s + e)
            mark = next((n for ms, me, n in marks if ms <= mid < me), None)
            label = " / ".join(x for x in (mark, covering_frame(frames, s, e)) if x) \
                or "no annotation or frame covers it"
        by_label[label] = by_label.get(label, 0.0) + (e - s) * 1e-9
    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]  # noqa: E731
    return {
        "window_s": window,
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / len(per_device),
        "idle_share": per_device[worst]["idle_share"],
        "idle_s": window - per_device[worst]["busy_s"],
        "worst_device": worst, "detail_device": names[0],
        "opens_in": opens_in, "cut_s": (lo - first) * 1e-9 if opens_in else 0.0,
        "boundaries": sum(1 for _s, _e, n in marks if n == "bench.boundary"),
        "annotations": len(marks), "idle_gaps_counted": len(gaps),
        "devices": per_device,
        "breakdown": {"device_ops": rank(per_device[worst]["ops"]),
                      "idle_gaps": rank(by_label)},
    }
