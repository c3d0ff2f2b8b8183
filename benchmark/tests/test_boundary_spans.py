"""`benchmark/boundary_spans.py` and the seven per-layer metrics that read the
epoch boundary by name: hand-made device and host events (nanoseconds, as
`test_program_spans.py` builds them) with every value worked out by hand,
the two identities, a trace without the new names (hand-made, and the one
recorded on a v5e from PR 24's tree), and the entries' schema."""

import gzip
import os

import pytest

from benchmark import boundary_spans as bs, harness, program_spans as ps, reduce_trace as rt

DATA = os.path.join(os.path.dirname(__file__), "data")
NS = 1e-6  # nanoseconds of the hand-made events -> the metrics' milliseconds


class _Run:
    """As much of `harness.Run` as a reader of the trace touches."""

    def __init__(self, trace=None, trace_path=None):
        self.trace, self.trace_path = trace, trace_path


def _read(name, run):
    return harness.layer_reader(name).read(run)


# -- hand-made events ------------------------------------------------------------------------

DRAW = "%fusion.1 = f32[4,8]{1,0} fusion(s32[4]{0} %p), kind=kCustom, calls=%c1"
MARGINS = "%fusion.2 = f32[4]{0} fusion(f32[4,8]{1,0} %p), kind=kOutput, calls=%c2"
TWIN = "%fusion.9 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%c9"
COPY = "%copy.1 = f32[8,4]{1,0} copy(f32[8,4]{0,1} %p)"
LAYOUT = "%fusion.20 = f32[2,128]{1,0} fusion(f32[200]{0} %p), kind=kLoop, calls=%c20"
WHILE = "%while.1 = (f32[], f32[]) while((f32[], f32[]) %t), condition=%cond, body=%body"
ROWS = "%copy.15 = f32[512,76]{1,0} copy(f32[512,76]{0,1} %p)"
EMARGINS = "%fusion.10 = f32[64]{0} fusion(f32[8]{0} %p), kind=kOutput, calls=%c10"
SLICE = "%dynamic-slice.1 = f32[1]{0} dynamic-slice(f32[2]{0} %p, s32[] %i)"
SQUEEZE = "%bitcast.1 = f32[] bitcast(f32[1]{0} %p)"
CONVERT = "%convert.1 = f32[8]{0} convert(f32[8]{0} %p)"
POW = "%multiply.1 = f32[8]{0} multiply(f32[8]{0} %p, f32[8]{0} %p)"
SUM = "%reduce.1 = f32[] reduce(f32[8]{0} %p, f32[] %z), dimensions={0}, to_apply=%add"
FOLD = "%fusion.30 = u32[2]{0} fusion(u32[2]{0} %k), kind=kLoop, calls=%c30"
PATHS = {"/device:TPU:0": {
    DRAW: ["jit(_epoch_shard)/while/body/dsgd.draw/gather:"],
    MARGINS: ["jit(_epoch_shard)/while/body/dsgd.margins/dot_general:"],
    # one instruction in both programs under two scopes: the evaluation's own path decides
    TWIN: ["jit(_epoch_shard)/while/body/dsgd.scatter/add:",
           "jit(_eval_shard)/dsgd.eval/while/body/closed_call/dsgd.eval_reduce/add:"],
    COPY: ["jit(_epoch_shard)/copy:"],
    LAYOUT: ["jit(_eval_shard)/dsgd.layout/jit(_pad)/pad:"],
    WHILE: ["jit(_eval_shard)/dsgd.eval/while"],
    ROWS: ["jit(_eval_shard)/dsgd.eval/while/body/closed_call/dsgd.eval_rows/dynamic_slice:"],
    EMARGINS: ["jit(_eval_shard)/dsgd.eval/while/body/closed_call/dsgd.margins/dot_general:"],
    SLICE: ["jit(dynamic_slice)/dynamic_slice:"], SQUEEZE: ["jit(squeeze)/squeeze:"],
    CONVERT: ["jit(convert_element_type)/convert_element_type:"],
    POW: ["jit(integer_pow)/integer_pow:"], SUM: ["jit(_reduce_sum)/reduce_sum:"],
    FOLD: ["jit(_threefry_fold_in)/threefry2x32:"]}}
# the same programs before PR 34 named their pieces: bare dsgd.eval around the margins
OLD_PATHS = {"/device:TPU:0": dict(PATHS["/device:TPU:0"], **{
    ROWS: ["jit(_eval_shard)/dsgd.eval/while/body/closed_call/dynamic_slice:"],
    TWIN: ["jit(_epoch_shard)/while/body/dsgd.scatter/add:",
           "jit(_eval_shard)/dsgd.eval/while/body/closed_call/add:"]})}


def _evaluation(t, chunks, layout_at, loop_own=0):
    """An evaluation program whose event opens at `t`: the layout of `w`,
    then a loop of `chunks` chunks of 95 ns (fetch 20, margins 60, sums 15)
    and `loop_own` ns of its own."""
    ops = [(layout_at, t + 10, LAYOUT), (t + 10, t + 10 + 95 * chunks + loop_own, WHILE)]
    for c in range(chunks):
        at = t + 10 + 95 * c
        ops += [(at, at + 20, ROWS), (at + 20, at + 80, EMARGINS), (at + 80, at + 95, TWIN)]
    return ops


def _crumbs(t):
    """The eager programs behind `float()` x 2 and `lam*||w||^2`, from the
    start `t` of the host's `.pull`: (start, end, program, its operation)."""
    return [(t + 5, t + 7, "jit_dynamic_slice(7)", SLICE), (t + 9, t + 10, "jit_squeeze(8)", SQUEEZE),
            (t + 25, t + 27, "jit_dynamic_slice(7)", SLICE), (t + 29, t + 30, "jit_squeeze(8)", SQUEEZE),
            (t + 50, t + 51, "jit_convert_element_type(9)", CONVERT),
            (t + 55, t + 58, "jit_integer_pow(10)", POW), (t + 65, t + 69, "jit__reduce_sum(11)", SUM)]


def _events(phased=True):
    """Ten steps of an epoch program, the train evaluation (three chunks),
    the test evaluation (two), their crumbs, a key fold, a boundary."""
    ops, modules = [], [(0, 1000, "jit__epoch_shard(1)"), (1200, 1500, "jit__eval_shard(2)"),
                        (1700, 1900, "jit__eval_shard(3)")]
    for i in range(10):
        t = 100 * i
        ops += [(t, t + 40, DRAW), (t + 40, t + 70, MARGINS), (t + 70, t + 80, TWIN),
                (t + 80, t + 90, COPY)]
    ops += _evaluation(1200, 3, 1200, loop_own=5) + _evaluation(1700, 2, 1705)
    eager = _crumbs(1505) + _crumbs(1905) + [
        (1602, 1603, "jit_squeeze(8)", SQUEEZE),  # between two evaluations' phases
        (2050, 2055, "jit__threefry_fold_in(12)", FOLD)]
    ops += [(s, e, op) for s, e, _program, op in eager]
    modules += [(s, e, program) for s, e, program, _op in eager]
    devices = {0: {rt.OPS_LINE: ops, rt.MODULES_LINE: modules}}
    marks = [(2150, 2200, "bench.boundary")]
    spans = [(-100, 1020, "trainer.epoch", {"epoch": 1}),
             (1050, 1600, "trainer.evaluate", {"epoch": 1, "split": "train"}),
             (1600, 2000, "trainer.evaluate", {"epoch": 1, "split": "test"}),
             (2000, 2100, "trainer.bookkeeping", {"epoch": 1}),
             (2100, 2200, "trainer.criterion", {"epoch": 1})]
    for t, woke in ((1060, 1505), (1605, 1905)):
        if phased:
            spans += [(t, t + 40, "trainer.evaluate.dispatch", {}),
                      (t + 40, woke, "trainer.evaluate.wait", {}),
                      (woke, woke + 45, "trainer.evaluate.pull", {}),
                      (woke + 45, woke + 90, "trainer.evaluate.reg", {})]
        else:  # the parent's two phases
            spans += [(t, t + 40, "trainer.evaluate.dispatch", {}),
                      (t + 40, woke + 90, "trainer.evaluate.pull", {})]
    return devices, marks, spans


def _run(phased=True, paths=PATHS):
    devices, marks, spans = _events(phased)
    trace = rt.reduce_events(devices, marks, opens_in="_epoch_shard")
    run = _Run(trace=trace, trace_path="unused")
    run.boundary_spans = bs.attribute(trace, devices, spans, marks[-1][1], paths)
    run.program_spans = ps.attribute(trace, devices, spans, marks[-1][1], paths)
    return run


# -- each metric's value -----------------------------------------------------------------------


def test_the_evaluation_programs_by_scope():
    run = _run()
    device = run.boundary_spans["device"]
    assert run.boundary_spans["named"] is True
    assert device["runs"] == 1 and device["eval_programs"] == 2 and device["scoped"]
    # five chunks: 60 of margins, 20 of fetch, 15 of sums each; the twin counts as the
    # evaluation's `dsgd.eval_reduce`, not as the epoch program's `dsgd.scatter`
    assert device["eval_ms_by_scope"] == pytest.approx({
        "dsgd.margins": 300 * NS, "dsgd.eval_rows": 100 * NS, "dsgd.eval_reduce": 75 * NS,
        "dsgd.layout": 15 * NS, "dsgd.eval": 5 * NS,     # the first loop's own 5 ns
        bs.NO_OPERATION: 5 * NS})                        # 1700-1705: nothing ran yet
    assert _read("eval_margins_ms", run) == pytest.approx(300 * NS)
    assert _read("eval_rows_ms", run) == pytest.approx(100 * NS)
    assert _read("eval_other_ms", run) == pytest.approx(100 * NS)
    assert device["ambiguous_ms"] == 0.0


def test_the_crumbs_by_program_and_by_host_phase():
    run = _run()
    device = run.boundary_spans["device"]
    assert _read("crumb_device_ms", run) == pytest.approx(34 * NS)
    assert device["crumb_ms_by_phase"] == pytest.approx({
        "trainer.evaluate.pull": 12 * NS, "trainer.evaluate.reg": 16 * NS,
        "trainer.bookkeeping": 5 * NS, bs.NO_PHASE: 1 * NS})
    assert device["crumb_ms_by_program"] == pytest.approx({
        "jit_dynamic_slice": 8 * NS, "jit_squeeze": 5 * NS, "jit_convert_element_type": 2 * NS,
        "jit_integer_pow": 6 * NS, "jit__reduce_sum": 8 * NS, "jit__threefry_fold_in": 5 * NS})


def test_every_program_run_is_counted():
    run = _run()
    device = run.boundary_spans["device"]
    assert _read("boundary_programs", run) == 19  # what three programs and one pull would read as 3
    assert device["programs_by_name"] == {
        "jit__epoch_shard": 1, "jit__eval_shard": 2, "jit_dynamic_slice": 4, "jit_squeeze": 5,
        "jit_convert_element_type": 2, "jit_integer_pow": 2, "jit__reduce_sum": 2,
        "jit__threefry_fold_in": 1}
    assert device["programs_by_phase"] == {
        "trainer.evaluate.wait": 2,  # an evaluation's event opens while the host waits for it
        "trainer.evaluate.pull": 8, "trainer.evaluate.reg": 6, "trainer.bookkeeping": 1,
        bs.NO_PHASE: 1}
    assert sum(device["programs_by_name"].values()) == device["boundary_programs"]
    assert sum(device["programs_by_phase"].values()) == device["boundary_programs"] - 1


def test_idle_time_by_phase():
    run = _run()
    idle = run.boundary_spans["idle"]
    # gaps: 1000-1200, then what lies between the programs up to 2200 (worked out in the
    # events: a pull's four crumbs leave 5+2+15+2+15 idle, a reg's three 5+4+7+21)
    assert idle["ms_by_phase"] == pytest.approx({
        "trainer.evaluate.dispatch": 80 * NS, "trainer.evaluate.wait": 165 * NS,
        "trainer.evaluate.pull": 78 * NS, "trainer.evaluate.reg": 74 * NS})
    assert _read("eval_pull_idle_ms", run) == pytest.approx(78 * NS)
    assert _read("eval_reg_idle_ms", run) == pytest.approx(74 * NS)
    assert run.boundary_spans["phase_spans"] == dict.fromkeys(bs.PHASES, 2)
    assert run.boundary_spans["host_ms_by_phase"] == pytest.approx({
        "trainer.evaluate.dispatch": 80 * NS, "trainer.evaluate.wait": 665 * NS,
        "trainer.evaluate.pull": 90 * NS, "trainer.evaluate.reg": 90 * NS})
    # the device's events start after the host's calls here, and end before the
    # waits do: the clocks may agree (a skew between -0.095 and 0.005 us)
    assert run.boundary_spans["clock_skew_us"] == pytest.approx(
        {"pairs": 2, "at_least": -0.095, "at_most": 0.005})


# -- the two identities ---------------------------------------------------------------------------


def test_the_device_pieces_sum_to_eval_device_ms():
    run = _run()
    device = run.boundary_spans["device"]
    pieces = sum(_read(name, run) for name in (
        "eval_margins_ms", "eval_rows_ms", "eval_other_ms", "crumb_device_ms"))
    assert pieces == pytest.approx(534 * NS)
    assert pieces == pytest.approx(_read("eval_device_ms", run))
    assert device["sum_ms"] == pytest.approx(pieces)
    assert device["eval_device_ms"] == pytest.approx(_read("eval_device_ms", run))
    assert device["identity_rel"] == pytest.approx(0.0, abs=1e-12)
    assert sum(device["eval_ms_by_scope"].values()) == pytest.approx(500 * NS)


def test_the_four_phases_idle_sums_to_eval_idle_ms():
    run = _run()
    idle = run.boundary_spans["idle"]
    assert idle["evaluate_ms"] == pytest.approx(_read("eval_idle_ms", run)) == pytest.approx(421 * NS)
    # 24 ns of the evaluations' idle lie in no phase: before the first dispatch and
    # between a reg's end and the next span's start.  On the chip the phases tile the call
    assert idle["unphased_ms"] == pytest.approx(24 * NS)
    assert sum(idle["ms_by_phase"].values()) + idle["unphased_ms"] == pytest.approx(
        _read("eval_idle_ms", run))
    # and the old split of the same gaps still holds beside it
    assert _read("eval_idle_ms", run) + _read("loop_idle_ms", run) == pytest.approx(
        _read("boundary_idle_ms", run))


# -- whose path decides a scope --------------------------------------------------------------------


def test_an_evaluations_own_path_decides_and_twins_inside_it_are_ambiguous():
    paths = {"%a": ["jit(_epoch_shard)/while/body/dsgd.scatter/add:",
                    "jit(_eval_shard)/dsgd.eval/while/body/dsgd.eval_reduce/add:"],
             "%b": ["jit(_eval_shard)/dsgd.eval/while/body/dsgd.eval_rows/copy:",
                    "jit(_eval_shard)/dsgd.eval/while/body/dsgd.eval_reduce/copy:"],
             "%c": ["jit(_eval_shard)/dsgd.eval/while/body/dsgd.eval_rows/copy:"] * 2,
             "%d": ["jit(other)/dsgd.margins/mul:"], "%e": ["jit(_eval_shard)/copy:"]}
    assert bs._eval_scope(paths, "%a") == "dsgd.eval_reduce"
    assert bs._eval_scope(paths, "%b") == ps.AMBIGUOUS
    assert bs._eval_scope(paths, "%c") == "dsgd.eval_rows"
    assert bs._eval_scope(paths, "%d") == "dsgd.margins"  # no path of its own: all of them
    assert bs._eval_scope(paths, "%e") is None and bs._eval_scope(paths, "%never") is None


def test_ambiguous_time_is_printed_and_counted_as_other():
    twice = {"/device:TPU:0": dict(PATHS["/device:TPU:0"], **{ROWS: [
        "jit(_eval_shard)/dsgd.eval/while/body/closed_call/dsgd.eval_rows/dynamic_slice:",
        "jit(_eval_shard)/dsgd.eval/while/body/closed_call/dsgd.margins/mul:"],
        # one instruction keeps the scope's name in the trace
        LAYOUT: ["jit(_eval_shard)/dsgd.eval/dsgd.eval_rows/pad:"]})}
    device = _run(paths=twice).boundary_spans["device"]
    assert device["ambiguous_ms"] == pytest.approx(100 * NS)
    assert device["eval_rows_ms"] == pytest.approx(15 * NS)
    assert device["eval_other_ms"] == pytest.approx(185 * NS)


def test_a_fetch_fused_away_reads_zero_not_none():
    """Dense rows, an output axis: the compiler fuses a chunk's slices into
    what consumes them and no operation runs under `dsgd.eval_rows`; the
    evaluation's other new name says the trace is of a tree that has both."""
    fused = {"/device:TPU:0": dict(PATHS["/device:TPU:0"], **{
        ROWS: ["jit(_eval_shard)/dsgd.eval/while/body/closed_call/dsgd.margins/dot_general:"]})}
    run = _run(paths=fused)
    assert run.boundary_spans["named"] is True
    assert _read("eval_rows_ms", run) == 0.0
    assert _read("eval_margins_ms", run) == pytest.approx(400 * NS)
    assert _read("eval_other_ms", run) == pytest.approx(100 * NS)


# -- a trace without the new names -------------------------------------------------------------------


@pytest.mark.parametrize("phased,paths", [(False, OLD_PATHS), (True, OLD_PATHS), (False, PATHS)])
def test_a_trace_without_the_new_names_reads_none(phased, paths):
    """The parent of the PR that added them (neither name), and a trace
    with one of the two: the run must not fail, the line leaves the
    metrics out, and what needs no new name is still printed."""
    run = _run(phased=phased, paths=paths)
    found = run.boundary_spans
    assert found["named"] is False
    for name in bs.METRICS:
        assert _read(name, run) is None, name
    assert found["device"]["sum_ms"] == pytest.approx(534 * NS)
    assert found["device"]["boundary_programs"] == 19
    if not phased:  # the parent's `.pull` holds the wait and the regulariser too
        assert found["phase_spans"]["trainer.evaluate.wait"] == 0
        assert found["clock_skew_us"] == pytest.approx(
            {"pairs": 2, "at_least": -0.095, "at_most": None})
        assert found["idle"]["ms_by_phase"]["trainer.evaluate.pull"] == pytest.approx(317 * NS)
    if paths is OLD_PATHS:
        assert "dsgd.eval_rows" not in found["device"]["eval_ms_by_scope"]
        assert found["device"]["eval_ms_by_scope"]["dsgd.eval"] == pytest.approx(180 * NS)


def test_runs_without_a_sync_trace_read_none(capsys):
    for run in (_Run(), _Run(trace={"opens_in": None, "window_s": 1.0}, trace_path="unused")):
        for name in bs.METRICS:
            assert _read(name, run) is None, name
    assert capsys.readouterr().out == ""  # nothing to say of a Hogwild run


def test_a_trace_that_cannot_be_read_is_printed_not_raised(tmp_path, capsys):
    path = tmp_path / "broken.xplane.pb"
    path.write_bytes(b"\x0f\x0f\x0f")  # wire type 7: not a protobuf
    run = _Run(trace={"opens_in": "_epoch_shard", "window_s": 1.0}, trace_path=str(path))
    assert bs.of(run) is None and bs.of(run) is None
    printed = capsys.readouterr().out.splitlines()
    assert len(printed) == 1 and printed[0].startswith('boundary_spans: {"error": ')
    assert _read("eval_margins_ms", run) is None and _read("eval_pull_idle_ms", run) is None


def test_the_trace_recorded_from_pr24s_tree_reads_none_and_keeps_its_sums(tmp_path, capsys):
    """`rcv1-sync-tiny-spans`: recorded on a v5e before the evaluation named
    its pieces and before `.wait` / `.reg`: the file is parsed (the wire
    reader, the events, the extra pass for the two new names), the readers
    answer None, and the sums that need no new name tie to the old metrics."""
    path = tmp_path / "sync.xplane.pb"
    with gzip.open(os.path.join(DATA, "rcv1-sync-tiny-spans.v5e.xplane.pb.gz"), "rb") as f:
        path.write_bytes(f.read())
    run = _Run(trace_path=str(path))
    run.trace = rt.reduce(run.trace_path, opens_in="_epoch_shard")
    for name in bs.METRICS:
        assert _read(name, run) is None, name
    printed = [line for line in capsys.readouterr().out.splitlines()
               if line.startswith("boundary_spans: ")]
    assert len(printed) == 1 and '"named": false' in printed[0]  # parsed once, printed once
    found = run.boundary_spans
    device = found["device"]
    assert device["runs"] == 3 and device["eval_programs"] == 2
    assert device["identity_rel"] == pytest.approx(0.0, abs=1e-9)
    assert device["eval_device_ms"] == pytest.approx(_read("eval_device_ms", run))
    assert device["boundary_programs"] == pytest.approx(17.0, abs=0.7)  # PR 22's seventeen
    assert "dsgd.eval_rows" not in device["eval_ms_by_scope"]
    assert device["ambiguous_ms"] == 0.0
    assert found["phase_spans"] == {
        "trainer.evaluate.dispatch": 6, "trainer.evaluate.wait": 0,
        "trainer.evaluate.pull": 6, "trainer.evaluate.reg": 0}
    # the old `.pull` held all of the evaluation's idle but the dispatch's
    assert found["idle"]["evaluate_ms"] == pytest.approx(_read("eval_idle_ms", run))
    assert found["idle"]["ms_by_phase"]["trainer.evaluate.pull"] == pytest.approx(8.9262973)


# -- the entries ------------------------------------------------------------------------------------


@pytest.mark.parametrize("name", bs.METRICS)
def test_each_new_entry_keeps_the_schemas_rules(name):
    bench = harness.load_benchmark(harness.ROOT)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    # every cell that reports the metric it moves, and no other: the sync cells
    (moved,) = [m for m in bench["end_to_end"] if m["name"] == entry["moves"]]
    assert entry["moves"] == "train_samples_per_s"
    assert entry["workloads"] == moved["workloads"] and len(entry["workloads"]) == 8
    for cell in entry["workloads"]:
        assert entry in harness.metrics_for(bench, "per_layer", cell)
        assert moved in harness.metrics_for(bench, "end_to_end", cell)
    assert entry["better"] == "lower"
    assert entry["unit"] == ("count" if name == "boundary_programs" else "ms")
    assert entry["source"] == ("program_span" if name.endswith("_idle_ms") else "device_trace")
    # a layer the benchmark already names, letter for letter
    before = bench["per_layer"][:-len(bs.METRICS)]
    assert entry["layer"] == "fit loop" and entry["layer"] in {m["layer"] for m in before}
    # the seven stand at the end of the list, in the helper's order, after all that was there
    assert [m["name"] for m in bench["per_layer"][-len(bs.METRICS):]] == list(bs.METRICS)
    assert not {m["name"] for m in before} & set(bs.METRICS)
    assert callable(harness.layer_reader(name).read)
    # the shared helper is no reader: it lives beside the readers' directory
    assert not os.path.exists(os.path.join(harness.ROOT, "benchmark", "layer_metrics",
                                           "boundary_spans.py"))
