"""`benchmark/program_spans.py` and the per-layer metrics that read the
program's scopes and spans by name: the wire reader against PR 22's trace
(no scope in it) and against two small traces recorded on a v5e from
PR 24's tree (scopes and spans in them, every new reader's value pinned),
and hand-made events for the attribution rules."""

import gzip
import os

import pytest

from benchmark import harness, program_spans as ps, reduce_trace as rt

DATA = os.path.join(os.path.dirname(__file__), "data")
NEW_METRICS = {
    "draw_us_per_step": "sync", "margins_us_per_step": "sync",
    "scatter_us_per_step": "sync", "unscoped_us_per_step": "sync",
    "eval_idle_ms": "sync", "loop_idle_ms": "sync",
    "async_pull_us_per_dispatch": "hogwild", "async_host_us_per_dispatch": "hogwild",
    "async_entry_device_us": "hogwild", "setup_compile_s": "all"}


class _Run:
    """As much of `harness.Run` as a reader of the trace touches."""

    def __init__(self, trace=None, trace_path=None, window_start=0.0):
        self.trace, self.trace_path, self.window_start = trace, trace_path, window_start


def _read(name, run):
    return harness.layer_reader(name).read(run)


def _unpacked(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / name[:-3]
    with gzip.open(os.path.join(DATA, name), "rb") as f:
        path.write_bytes(f.read())
    return str(path)


# -- the wire reader ----------------------------------------------------------------------


def test_the_wire_reader_finds_the_name_stack_of_pr22s_gather(tmp_path_factory):
    path = _unpacked(tmp_path_factory, "rcv1-sync-tiny.v5e.xplane.pb.gz")
    planes = ps.read_paths(path)
    assert "/device:TPU:0" in planes and "/host:CPU" not in planes
    paths = planes["/device:TPU:0"]
    (gather,) = [v for k, v in paths.items() if k.startswith("%fusion.61 = ")]
    assert gather == ["jit(_epoch_shard)/while/body/closed_call/vmap()/dot_general:"]
    # PR 22's program had no scope: every path reads as unscoped
    assert {ps.scope_of(p) for v in paths.values() for p in v} == {None}


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, payload):
    """A varint field for an int, a length-delimited one for bytes."""
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    return _varint((number << 3) | 2) + _varint(len(payload)) + payload


def _plane(name, events, stat_names):
    """An XPlane: events {id: (name, [(stat id, str | ref id)])}."""
    body = _field(1, 7) + _field(2, name.encode())
    body += _field(3, _field(2, b"XLA Ops") + _varint((3 << 3) | 1) + b"\0" * 8)  # a line, skipped
    for key, (event_name, stats) in events.items():
        meta = _field(1, key) + _field(2, event_name.encode())
        for stat_id, value in stats:
            stat = _field(1, stat_id)
            stat += _field(5, value.encode()) if isinstance(value, str) else _field(7, value)
            meta += _field(5, stat)
        body += _field(4, _field(1, key) + _field(2, meta))
    for key, stat_name in stat_names.items():
        body += _field(5, _field(1, key) + _field(2, _field(1, key) + _field(2, stat_name.encode())))
    return _field(1, body)


def test_the_wire_reader_on_a_hand_made_xspace(tmp_path):
    stats = {1: "tf_op", 2: "flops", 3: "jit(f)/dsgd.scatter/dot_general:"}
    device = _plane("/device:TPU:0", {
        10: ("%fusion.1 = f32[4]", [(2, 0), (1, "jit(f)/while/body/dsgd.draw/gather:")]),
        11: ("%fusion.2 = f32[4]", [(1, 3)]),                       # by reference
        12: ("%twin = f32[4]", [(1, "jit(f)/dsgd.eval/dsgd.margins/dot_general:")]),
        13: ("%twin = f32[4]", [(1, "jit(g)/dsgd.scatter/dot_general:")]),
        14: ("%same = f32[4]", [(1, "jit(f)/dsgd.update/sub:")]),
        15: ("%same = f32[4]", [(1, "jit(g)/dsgd.update/sub:")]),
        16: ("%copy.1 = f32[4]", [(2, 0)]),                         # no tf_op at all
        17: ("%while = (f32[4])", [(1, "jit(f)/while")])}, stats)
    host = _plane("/host:CPU", {1: ("trainer.epoch", [(1, "not/a/device")])}, stats)
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(device + host)
    planes = ps.read_paths(str(path))
    assert list(planes) == ["/device:TPU:0"]
    paths = planes["/device:TPU:0"]
    scope = lambda name: ps.scope_of_event(paths, name)  # noqa: E731
    assert scope("%fusion.1 = f32[4]") == "dsgd.draw"
    assert scope("%fusion.2 = f32[4]") == "dsgd.scatter"
    assert scope("%twin = f32[4]") == ps.AMBIGUOUS         # twins that disagree
    assert scope("%same = f32[4]") == "dsgd.update"        # twins that agree
    assert scope("%copy.1 = f32[4]") is None and scope("%while = (f32[4])") is None
    assert scope("%never.seen") is None


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(_epoch_shard)/while/body/closed_call/dsgd.margins/dot_general:", "dsgd.margins"),
    ("jit(_eval_shard)/dsgd.eval/while/body/closed_call/dsgd.margins/mul", "dsgd.margins"),
    ("jit(_epoch_shard)/while/body/vmap(dsgd.scatter)/dot_general:", "dsgd.scatter"),
    ("dsgd.margins/reduce_sum", "dsgd.margins"),
    ("jit(_epoch_shard)/while/body/closed_call/vmap()/dot_general:", None),
    ("jit(dsgd_like)/dsgdXmargins", None), ("", None), (None, None)])
def test_scope_of_is_the_innermost_dsgd_component(tf_op, scope):
    assert ps.scope_of(tf_op) == scope


def test_interval_helpers():
    assert ps.merged([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert ps.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert ps.overlap([(0, 10)], []) == 0 and ps.overlap([(0, 1)], [(1, 2)]) == 0


# -- attribution, on hand-made events (nanoseconds) --------------------------------------------

DRAW = "%fusion.1 = f32[4,8]{1,0} fusion(s32[4]{0} %p), kind=kCustom, calls=%c1"
MARGINS = "%fusion.2 = f32[4]{0} fusion(f32[4,8]{1,0} %p), kind=kOutput, calls=%c2"
TWIN = "%fusion.9 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%c9"
COPY = "%copy.1 = f32[8,4]{1,0} copy(f32[8,4]{0,1} %p)"
EVAL = "%fusion.10 = f32[64]{0} fusion(f32[8]{0} %p), kind=kOutput, calls=%c10"
PATHS = {"/device:TPU:0": {
    DRAW: ["jit(_epoch_shard)/while/body/dsgd.draw/gather:"],
    MARGINS: ["jit(_epoch_shard)/while/body/dsgd.margins/dot_general:"],
    TWIN: ["jit(_epoch_shard)/while/body/dsgd.scatter/add:",
           "jit(_eval_shard)/dsgd.eval/add:"],
    COPY: ["jit(_epoch_shard)/copy:"],
    EVAL: ["jit(_eval_shard)/dsgd.eval/while/body/dsgd.margins/dot_general:"]}}


def _sync_events():
    """Ten steps of 100 ns in an epoch program that was running when the
    profiler came up (no `%while`), two evaluation programs, a boundary."""
    ops = []
    for i in range(10):
        t = 100 * i
        ops += [(t, t + 40, DRAW), (t + 40, t + 70, MARGINS), (t + 70, t + 80, TWIN),
                (t + 80, t + 90, COPY)]
    ops += [(1200, 1500, EVAL), (1700, 1900, EVAL)]
    modules = [(0, 1000, "jit__epoch_shard(1)"), (1200, 1500, "jit__eval_shard(2)"),
               (1700, 1900, "jit__eval_shard(3)")]
    devices = {0: {rt.OPS_LINE: ops, rt.MODULES_LINE: modules}}
    marks = [(2150, 2200, "bench.boundary")]
    spans = [(-100, 1020, "trainer.epoch", {"epoch": 1}),  # began before the window
             (1050, 1600, "trainer.evaluate", {"epoch": 1, "split": "train"}),
             (1060, 1100, "trainer.evaluate.dispatch", {}),
             (1100, 1590, "trainer.evaluate.pull", {}),
             (1600, 2000, "trainer.evaluate", {"epoch": 1, "split": "test"}),
             (2000, 2100, "trainer.bookkeeping", {"epoch": 1}),
             (2100, 2200, "trainer.criterion", {"epoch": 1})]
    return devices, marks, spans


def _sync_run(spans=None, paths=PATHS):
    devices, marks, made = _sync_events()
    trace = rt.reduce_events(devices, marks, opens_in="_epoch_shard")
    run = _Run(trace=trace, trace_path="unused")
    run.program_spans = ps.attribute(
        trace, devices, made if spans is None else spans, marks[-1][1], paths)
    return run


def test_scopes_sum_to_the_programs_busy_time_and_twins_are_ambiguous():
    run = _sync_run()
    program = run.program_spans["program"]
    assert program["steps"] == 10 and program["step_us"] == pytest.approx(0.1)
    assert program["us_per_step"] == pytest.approx({
        "dsgd.draw": 0.040, "dsgd.margins": 0.030, ps.AMBIGUOUS: 0.010,
        ps.UNSCOPED: 0.020})  # the copy, and 10 ns a step that no operation covers
    assert sum(program["us_per_step"].values()) == pytest.approx(program["busy_us_per_step"])
    assert run.program_spans["ambiguous_us_per_step"] == pytest.approx(0.010)
    assert _read("draw_us_per_step", run) == pytest.approx(0.040)
    assert _read("margins_us_per_step", run) == pytest.approx(0.030)
    assert _read("scatter_us_per_step", run) == pytest.approx(0.0)
    assert _read("unscoped_us_per_step", run) == pytest.approx(0.020)
    # the old class metric counts the same operations by opcode: both divide by the same steps
    assert rt.class_us_per_step(run.trace["devices"]["TPU:0"], "matmul") == pytest.approx(0.030)


def test_gaps_are_split_by_the_span_that_covers_them():
    run = _sync_run()
    idle = run.program_spans["idle"]
    assert idle["runs"] == 1 and idle["gaps"] == 3 and idle["evaluate_spans"] == 2
    # gaps: 1000-1200, 1500-1700, 1900-2200
    assert idle["total_ms"] == pytest.approx(700e-6)
    assert idle["ms_per_epoch"] == pytest.approx({
        "trainer.epoch": 20e-6, "trainer.evaluate": 450e-6,
        "trainer.evaluate.dispatch": 40e-6, "trainer.evaluate.pull": 190e-6,
        "trainer.bookkeeping": 100e-6, "trainer.criterion": 100e-6, "ckpt.save": 0.0,
        "no span": 30e-6})
    assert _read("eval_idle_ms", run) == pytest.approx(450e-6)
    assert _read("loop_idle_ms", run) == pytest.approx(250e-6)
    # the two are the old metric, split
    dev = run.trace["devices"]["TPU:0"]
    boundary_idle_ms = 1e3 * dev["between"]["idle_s"] / dev["program"]["runs"]
    assert _read("eval_idle_ms", run) + _read("loop_idle_ms", run) == pytest.approx(boundary_idle_ms)


def test_a_program_without_scopes_or_spans_reads_none():
    """The parent of the PR that added them: the run must not fail, and the
    line leaves the metric out."""
    unscoped = {"/device:TPU:0": {k: ["jit(_epoch_shard)/while/body/closed_call/add"]
                                  for k in PATHS["/device:TPU:0"]}}
    run = _sync_run(spans=[], paths=unscoped)
    assert run.program_spans["program"]["scoped"] is False
    for name, kind in NEW_METRICS.items():
        if kind != "all":
            assert _read(name, run) is None, name
    for name, kind in NEW_METRICS.items():  # neither does a run without a trace
        if kind != "all":
            assert _read(name, _Run()) is None, name


def test_a_trace_that_cannot_be_read_is_printed_not_raised(tmp_path, capsys):
    path = tmp_path / "broken.xplane.pb"
    path.write_bytes(b"\x0f\x0f\x0f")  # wire type 7: not a protobuf
    run = _Run(trace={"window_s": 1.0}, trace_path=str(path))
    assert ps.of(run) is None and ps.of(run) is None
    printed = capsys.readouterr().out.splitlines()
    assert len(printed) == 1 and printed[0].startswith('program_spans: {"error": ')
    assert _read("draw_us_per_step", run) is None and _read("eval_idle_ms", run) is None


KSTEP_COPY = "%copy.3 = f32[64,8]{1,0} copy(f32[64,8]{0,1} %p)"
KSTEP_WHILE = "%while = (f32[8]{0}) while((f32[8]{0}) %t), condition=%cond, body=%body"
KSTEP_PATHS = {"/device:TPU:0": {
    KSTEP_COPY: ["jit(kstep)/copy:"], KSTEP_WHILE: ["jit(kstep)/while"],
    DRAW: ["jit(kstep)/while/body/dsgd.draw/gather:"],
    MARGINS: ["jit(kstep)/while/body/dsgd.margins/dot_general:"]}}


def _async_run(paths=KSTEP_PATHS, with_spans=True):
    """Two workers, two dispatches each: 1000 ns iterations of which the
    pull is 700 (worker 0) and 500 (worker 1); each `jit_kstep` run copies
    its shard for 300 ns and then steps for 200."""
    ops, modules, spans = [], [], []
    for n, t in enumerate((0, 600, 1200, 1800)):
        modules.append((t, t + 500, f"jit_kstep({n})"))
        ops += [(t, t + 300, KSTEP_COPY), (t + 300, t + 500, KSTEP_WHILE),
                (t + 320, t + 400, DRAW), (t + 400, t + 480, MARGINS)]
    for worker, pull in ((0, 700), (1, 500)):
        for dispatch in (5, 6):
            t = 100 + 1000 * (dispatch - 5) + 10 * worker
            ids = {"worker": worker, "dispatch": dispatch}
            spans += [(t, t + 1000, "slave.async.iteration", ids),
                      (t + 10, t + 100, "slave.async.step", ids),
                      (t + 200, t + 200 + pull, "slave.async.pull", ids),
                      (t + 900, t + 990, "slave.async.push", ids)]
    # an iteration the window cuts is not counted
    spans.append((2150, 2500, "slave.async.iteration", {"worker": 0, "dispatch": 7}))
    spans.append((400, 900, "master.async.check", {"updates": 40}))
    devices = {0: {rt.OPS_LINE: ops, rt.MODULES_LINE: modules}}
    marks = [(0, 2300, "bench.second")]
    trace = rt.reduce_events(devices, marks)
    run = _Run(trace=trace, trace_path="unused")
    run.program_spans = ps.attribute(
        trace, devices, spans if with_spans else [], marks[-1][1], paths)
    return run


def test_hogwild_dispatches_by_their_spans_and_the_entry_copy_by_its_scope():
    run = _async_run()
    found = run.program_spans
    assert found["program"] is None and found["idle"] is None
    assert found["async"]["iterations"] == 4 and found["async"]["workers"] == 2
    assert found["async"]["checks"] == 1
    assert _read("async_pull_us_per_dispatch", run) == pytest.approx(0.6)
    assert _read("async_host_us_per_dispatch", run) == pytest.approx(0.4)
    assert found["async"]["phase_us"]["slave.async.drain"] == 0.0  # absent: printed as 0
    # 1e6 / (pull + host) x workers is the dispatch rate: 2 per microsecond-thousand here
    assert found["kstep"]["runs"] == 4
    # the copy (300) and the loop's own control (200 - 80 - 80 = 40)
    assert _read("async_entry_device_us", run) == pytest.approx(0.340)
    assert found["kstep"]["us_per_run"]["dsgd.draw"] == pytest.approx(0.080)


def test_hogwild_readers_answer_none_without_spans_or_scopes():
    unscoped = {"/device:TPU:0": {k: ["jit(kstep)/while/body/add"]
                                  for k in KSTEP_PATHS["/device:TPU:0"]}}
    run = _async_run(paths=unscoped, with_spans=False)
    for name, kind in NEW_METRICS.items():
        if kind == "hogwild":
            assert _read(name, run) is None, name


# -- two small traces recorded on a v5e from PR 24's tree ---------------------------------------
#
# `rcv1-sync-tiny-spans`: `run.py --workload rcv1-sync-1chip --rehearse --trace 1` on the chip
# (three epochs of 82 steps at rehearsal size, full width, through the benchmark's own hook,
# whose shortest kept trace was cut from 0.2 s to 0.06 s for the recording).
# `hogwild-tiny-spans`: `HogwildEngine.fit`, two workers on the same rows, 0.12 s of steady
# state under one `bench.second` annotation, python tracer off.


def _recorded(tmp_path_factory, name, opens_in):
    run = _Run(trace_path=_unpacked(tmp_path_factory, name))
    run.trace = rt.reduce(run.trace_path, opens_in=opens_in)
    return run


@pytest.fixture(scope="module")
def sync_run(tmp_path_factory):
    return _recorded(tmp_path_factory, "rcv1-sync-tiny-spans.v5e.xplane.pb.gz", "_epoch_shard")


@pytest.fixture(scope="module")
def hogwild_run(tmp_path_factory):
    return _recorded(tmp_path_factory, "hogwild-tiny-spans.v5e.xplane.pb.gz", None)


@pytest.mark.parametrize("name,value", [
    ("draw_us_per_step", 7.8515244), ("margins_us_per_step", 42.7158699),
    ("scatter_us_per_step", 27.6897927), ("unscoped_us_per_step", 5.2136382),
    ("eval_idle_ms", 9.27972), ("loop_idle_ms", 1.221508),
    ("async_pull_us_per_dispatch", None), ("async_host_us_per_dispatch", None),
    ("async_entry_device_us", None)])
def test_each_reader_on_the_recorded_sync_trace(sync_run, name, value):
    got = _read(name, sync_run)
    assert got is None if value is None else got == pytest.approx(value, rel=1e-6)


@pytest.mark.parametrize("name,value", [
    ("async_pull_us_per_dispatch", 460.500323), ("async_host_us_per_dispatch", 3249.523855),
    ("async_entry_device_us", 16.6249846),
    ("draw_us_per_step", None), ("margins_us_per_step", None), ("scatter_us_per_step", None),
    ("unscoped_us_per_step", None), ("eval_idle_ms", None), ("loop_idle_ms", None)])
def test_each_reader_on_the_recorded_hogwild_trace(hogwild_run, name, value):
    got = _read(name, hogwild_run)
    assert got is None if value is None else got == pytest.approx(value, rel=1e-6)


def test_the_recorded_sync_trace_ties_the_new_numbers_to_the_old(sync_run, capsys):
    found = ps.of(sync_run)
    assert capsys.readouterr().out.count("program_spans: ") <= 1  # parsed once, printed once
    program, dev = found["program"], sync_run.trace["devices"]["TPU:0"]
    assert program["steps"] == dev["program"]["step"]["steps"] == 246
    # every scope of the mxu step but `dsgd.update`, which the compiler fused away
    assert set(program["us_per_step"]) == {
        "dsgd.draw", "dsgd.onehot", "dsgd.margins", "dsgd.coeff", "dsgd.scatter",
        "dsgd.regularize", "dsgd.allreduce", "dsgd.layout", ps.UNSCOPED}
    assert found["ambiguous_us_per_step"] == 0.0
    # 1. the pieces sum to the program's busy time, which is the step the reducer counts
    assert sum(program["us_per_step"].values()) == pytest.approx(program["busy_us_per_step"])
    assert program["busy_us_per_step"] == pytest.approx(program["step_us"], rel=0.01)
    # 2. the matmul class lies under the two products' scopes, whole
    by_class = program["class_us_per_step"]
    assert by_class["dsgd.margins/matmul"] + by_class["dsgd.scatter/matmul"] == pytest.approx(
        rt.class_us_per_step(dev, "matmul"))
    assert not [k for k in by_class if k.endswith("/matmul")
                and k.split("/")[0] not in ("dsgd.margins", "dsgd.scatter")]
    assert by_class["dsgd.margins/matmul"] == pytest.approx(41.5242398)
    assert by_class["dsgd.scatter/matmul"] == pytest.approx(27.0063577)
    # 3. the two idle metrics split the old one
    boundary_idle_ms = 1e3 * dev["between"]["idle_s"] / dev["program"]["runs"]
    assert _read("eval_idle_ms", sync_run) + _read("loop_idle_ms", sync_run) == pytest.approx(
        boundary_idle_ms)
    idle = found["idle"]
    assert idle["runs"] == 3 and idle["evaluate_spans"] == 6
    # the pulls are where the evaluation waits.  The profiler came up during an
    # evaluation here, so all three epoch programs and their spans are whole; the last
    # `trainer.criterion` was still open when the hook stopped the profiler from inside it
    assert idle["ms_per_epoch"]["trainer.evaluate.pull"] == pytest.approx(8.9262973)
    assert found["spans_in_window"] == {
        "trainer.bookkeeping": 3, "trainer.criterion": 2, "trainer.epoch": 3,
        "trainer.evaluate": 6, "trainer.evaluate.dispatch": 6, "trainer.evaluate.pull": 6}


def test_the_recorded_hogwild_trace_ties_spans_to_the_devices_count(hogwild_run):
    found = ps.of(hogwild_run)
    spans, kstep = found["async"], found["kstep"]
    assert spans["workers"] == 2 and spans["iterations"] == 62
    assert kstep["runs"] == hogwild_run.trace["devices"]["TPU:0"]["modules"]["jit_kstep"][0] == 65
    # 4. dispatches by the spans' mean against the device's own count of `jit_kstep`
    # runs in the window (whole iterations and the edges' parts)
    by_spans = 1e6 / spans["iteration_us"] * spans["workers"]
    by_device = kstep["runs"] / hogwild_run.trace["window_s"]
    assert by_spans == pytest.approx(by_device, rel=0.03)
    assert sum(spans["phase_us"].values()) <= spans["iteration_us"]
    assert set(kstep["us_per_run"]) == {
        "None", "dsgd.draw", "dsgd.onehot", "dsgd.margins", "dsgd.coeff", "dsgd.scatter",
        "dsgd.regularize"}


# -- the compile log ----------------------------------------------------------------------------


def test_setup_compile_s_sums_what_compiled_before_the_window(monkeypatch):
    from distributed_sgd_tpu import compile_cache

    monkeypatch.setattr(compile_cache, "_compiles", [
        (10.0, "jit(_epoch_shard)", 2.5, False), (11.0, "jit(_epoch_shard)", 0.5, True),
        (12.0, "jit(_eval_shard)", 1.0, False), (30.0, "jit(late)", 9.0, False)])
    assert _read("setup_compile_s", _Run(window_start=20.0)) == pytest.approx(4.0)
    # a commit whose compile_cache keeps no list
    monkeypatch.delattr(compile_cache, "compiles")
    assert _read("setup_compile_s", _Run(window_start=20.0)) is None


# -- the entries --------------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_each_new_entry_keeps_the_schemas_rules(name):
    bench = harness.load_benchmark(harness.ROOT)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert set(entry) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    sync = {"rcv1-sync-1chip", "rcv1-sync-4chip", "epsilon-sync-1chip"}
    cells = {"sync": sync, "hogwild": {"rcv1-hogwild-1chip"},
             "all": {w["name"] for w in bench["workloads"]}}[NEW_METRICS[name]]
    assert set(entry.get("workloads", cells)) == cells
    for cell in cells:  # the metric it moves is reported wherever it is
        assert entry["moves"] in [m["name"] for m in harness.metrics_for(bench, "end_to_end", cell)]
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"][:14]}  # a layer PR 22 named
    assert callable(harness.layer_reader(name).read)
    # new entries stand at the end of the list, after all of PR 22's
    assert bench["per_layer"].index(entry) >= 14
    # the shared helper is no reader: it lives beside the readers' directory
    assert not os.path.exists(os.path.join(harness.ROOT, "benchmark", "layer_metrics",
                                           "program_spans.py"))
