"""The cell `kdd2012-ftrl-sync-1chip`: its entries in BENCHMARK.json, its
configuration's statements, the two readers it adds on recorded and on
empty runs, and its rehearsal on the CPU."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

from benchmark import harness
from benchmark.harness import ROOT
from benchmark.layer_metrics import ftrl_step_roofline, ftrl_us_per_step

CELL = "kdd2012-ftrl-sync-1chip"

RECORDED = {"program": {"steps": 1700, "scoped": True,
                        "us_per_step": {"dsgd.draw": 5.9, "dsgd.ftrl": 3.2,
                                        "dsgd.margins": 25.0, "dsgd.scatter": 80.0}}}


def _run(spans, engine=None, trace=None):
    return SimpleNamespace(trace=trace or {"devices": {}}, trace_path="recorded",
                           program_spans=spans, engine=engine or {},
                           ctx=SimpleNamespace(peaks={"hbm_bps": 819e9}))


def test_the_scope_reader_takes_dsgd_ftrl_and_nothing_else():
    assert ftrl_us_per_step.read(_run(RECORDED)) == 3.2
    # a program without the scope (the parent, another optimizer): nothing, no raise
    other = {"program": dict(RECORDED["program"], us_per_step={"dsgd.draw": 5.9})}
    assert ftrl_us_per_step.read(_run(other)) is None
    unscoped = {"program": dict(RECORDED["program"], scoped=False, us_per_step={})}
    assert ftrl_us_per_step.read(_run(unscoped)) is None
    assert ftrl_us_per_step.read(SimpleNamespace(trace=None, trace_path=None)) is None


def test_the_roofline_reader_needs_the_engines_optimizer_and_a_step():
    trace = {"worst_device": "d", "devices": {"d": {"program": {"step": {"seconds": 100e-6}}}}}
    engine = {"batch_size": 100, "virtual_workers": 4, "row_width": 11, "dense": False,
              "optimizer": "ftrl"}
    share = ftrl_step_roofline.read(_run(RECORDED, engine, trace))
    assert 0.25 < share < 0.27  # 212,800 B at 819 GB/s over a 100 us step
    for missing in ("optimizer", "virtual_workers"):
        cut = {k: v for k, v in engine.items() if k != missing}
        assert ftrl_step_roofline.read(_run(RECORDED, cut, trace)) is None
    assert ftrl_step_roofline.read(_run(RECORDED, dict(engine, optimizer="sgd"), trace)) is None
    assert ftrl_step_roofline.read(_run(RECORDED, engine, {"worst_device": "d", "devices": {
        "d": {"program": None}}})) is None
    assert ftrl_step_roofline.read(SimpleNamespace(trace=None, ctx=SimpleNamespace(peaks=None))) is None


def _named(bench, kind, name):
    return next(e for e in bench[kind] if e["name"] == name)


def test_the_cell_appends_and_its_metrics_name_it_alone():
    """Found by name, not by place: a later PR appends its own entries."""
    bench = harness.load_benchmark(ROOT)
    cell = harness.load_cell(bench, CELL, ROOT)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "kdd2012-ftrl", "sync-4w-b100-ftrl")
    assert _named(bench, "configs", "kdd2012-ftrl")["reduced"] == ["rows"]
    new = {name: _named(bench, "per_layer", name)
           for name in ("ftrl_us_per_step", "ftrl_step_roofline")}
    assert all(m["workloads"] == [CELL] and m["layer"] == "kernels"
               and m["moves"] == "train_samples_per_s" for m in new.values())
    plain = {m["name"] for m in harness.metrics_for(bench, "end_to_end", CELL)}
    assert plain == {"train_samples_per_s", "setup_s"}
    traced = {m["name"] for m in harness.metrics_for(bench, "per_layer", CELL)}
    kdd = {m["name"] for m in harness.metrics_for(bench, "per_layer", "kdd2012-sync-1chip")}
    assert traced == (kdd - {"gather_scatter_roofline", "entry_step_roofline"}) | set(new)
    cells = [w["name"] for w in bench["workloads"]]
    before = set(cells[:cells.index(CELL)])  # the cells the benchmark had when the cell came
    for m in bench["end_to_end"] + bench["per_layer"]:  # appended: after every older cell listed
        listed = list(m.get("workloads", ()))
        if CELL in listed:
            assert listed.count(CELL) == 1
            assert all(c not in before for c in listed[listed.index(CELL) + 1:]), m["name"]
    with open(os.path.join(ROOT, "benchmark", "traffic", "sync-4w-b100.json")) as f:
        flat = json.load(f)
    for key in ("node_count", "batch_size", "sampling", "warm_epochs"):
        assert cell.traffic[key] == flat[key]
    assert cell.traffic["engine"] == "sync_ftrl"


def test_the_configuration_is_kdd2012_logistics_rows_under_ftrl():
    bench = harness.load_benchmark(ROOT)
    cfg = harness.load_cell(bench, CELL, ROOT).config
    kdd = harness.load_cell(bench, "kdd2012-sync-1chip", ROOT).config
    assert cfg["data"] == kdd["data"] and cfg["lam"] == kdd["lam"]
    assert (cfg["model"], cfg["regularizer"], cfg["generator"]) == ("logistic", "l2", "kdd2012_like")
    assert cfg["ftrl"]["beta"] == 1.0 and cfg["ftrl"]["l1"] > 0 and cfg["learning_rate"] > 0
    tol = cfg["tolerance"]
    for key in ("step_z_rel", "step_n_rel", "step_w_rel", "threshold_guard", "eval_loss_abs",
                "eval_penalty_rel", "eval_acc_abs"):
        assert 0 < float(tol[key]) < 1e-2
    assert set(cfg["reduced"]) == {"rows"}


def test_the_cell_rehearses_on_the_cpu_without_a_fault():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seed", "4300000077", "--rehearse"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    last = done.stdout.strip().splitlines()[-1]
    said = json.loads(last[len("rehearsal: "):])
    assert said["failed"] == 0 and said["attempted"] >= 1
    assert said["would_report"] == ["setup_s", "train_samples_per_s"]

    def printed(label):
        return next(json.loads(line[len(label) + 2:]) for line in done.stdout.splitlines()
                    if line.startswith(label + ": "))

    # every check but the quality band, which is the chip's at full size
    checks = printed("checks")
    step, evaluation = checks["step_vs_reference"], checks["evaluation_vs_reference"]
    for k in ("z", "n", "w"):
        assert step[f"{k}_rel_err"] <= step["tol"][k]
    assert step["moved_off_the_reference"] == 0 and step["coordinates_moved"] > 1000
    assert evaluation["loss_abs_err"] <= evaluation["loss_tol"]
    assert evaluation["penalty_rel_err"] <= evaluation["penalty_tol"]
    assert {"budget_loss", "loss_band", "budget_mean_loss", "mean_loss_band"} <= set(
        checks["quality_at_budget"])
    state = checks["guarantees"]["state"]
    assert state["finite_state"] and state["n_min"] >= 0
    assert state["nonzero"] == state["reference_nonzero"] and state["nonzero"] < state["touched"]
    engine = printed("engine")
    assert (engine["kernel"], engine["update"], engine["optimizer"]) == ("gather", "sparse", "ftrl")
    assert (engine["row_width"], engine["n_features"]) == (11, 54686452)
