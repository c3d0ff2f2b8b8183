"""The cell `amazoncat13k-sync-1chip`: its entries in BENCHMARK.json, the
reader of `labels_us_per_step` on a recorded `program_spans` dict, its
configuration's statements, and its rehearsal on the CPU."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmark import harness
from benchmark.harness import ROOT
from benchmark.layer_metrics import labels_us_per_step, row_step_roofline

CELL = "amazoncat13k-sync-1chip"

# what `program_spans.parse` printed for the cell's traced run on the chip,
# seed 3600000031, cut to what the readers take (my chip run, PR 36)
RECORDED = {"program": {"steps": 65, "scoped": True, "busy_us_per_step": 3134.1468153846154,
                        "us_per_step": {"dsgd.allreduce": 38.40535384615386,
                                        "dsgd.draw": 15.626753846153848,
                                        "dsgd.labels": 0.9268923076923077,
                                        "dsgd.layout": 38.22112307692308,
                                        "dsgd.margins": 531.3998461538462,
                                        "dsgd.rescale": 77.23315384615384,
                                        "dsgd.scatter": 2064.2195076923076,
                                        "unscoped": 368.11418461538466}}}


def _run(spans):
    return SimpleNamespace(trace={"devices": {}}, trace_path="recorded", program_spans=spans)


def test_the_reader_takes_the_labels_scope_and_nothing_else():
    assert labels_us_per_step.read(_run(RECORDED)) == 0.9268923076923077
    # a program without the scope (the parent, a cell with dense labels): nothing, no raise
    other = {"program": dict(RECORDED["program"], us_per_step={"dsgd.draw": 15.6})}
    assert labels_us_per_step.read(_run(other)) is None
    unscoped = {"program": dict(RECORDED["program"], scoped=False, us_per_step={})}
    assert labels_us_per_step.read(_run(unscoped)) is None
    assert labels_us_per_step.read(SimpleNamespace(trace=None, trace_path=None)) is None


def test_the_cell_appends_and_edits_nothing():
    bench = harness.load_benchmark(ROOT)
    cell = harness.load_cell(bench, CELL, ROOT)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "amazoncat13k-dismec", "sync-4w-b100-lists")
    assert bench["workloads"][-1]["name"] == CELL and bench["configs"][-1]["name"] == cell.config_name
    assert bench["configs"][-1]["reduced"] == ["labels_held"]
    assert bench["per_layer"][-1] == {
        "name": "labels_us_per_step", "unit": "us", "better": "lower", "source": "device_trace",
        "layer": "kernels", "moves": "train_samples_per_s", "workloads": [CELL]}
    plain = {m["name"] for m in harness.metrics_for(bench, "end_to_end", CELL)}
    assert plain == {"train_samples_per_s", "setup_s"}
    traced = {m["name"] for m in harness.metrics_for(bench, "per_layer", CELL)}
    topics = {m["name"] for m in harness.metrics_for(bench, "per_layer", "rcv1-topics-sync-1chip")}
    assert traced == topics | {"labels_us_per_step"}
    for m in bench["end_to_end"] + bench["per_layer"]:  # appended: the cell closes every list it is on
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL
    # sync-4w-b100's numbers letter for letter
    with open(os.path.join(ROOT, "benchmark", "traffic", "sync-4w-b100.json")) as f:
        flat = json.load(f)
    for key in ("node_count", "batch_size", "sampling", "warm_epochs"):
        assert cell.traffic[key] == flat[key]
    assert cell.traffic["engine"] == "sync_lists"


def test_the_configuration_states_its_widths_and_its_deployment():
    cfg = harness.load_cell(harness.load_benchmark(ROOT), CELL, ROOT).config
    data = cfg["data"]
    assert (cfg["model"], cfg["regularizer"], cfg["n_outputs"]) == ("squared_hinge", "l2", 1000)
    assert (data["n_features"], data["nnz"], data["n_labels_published"]) == (203882, 72, 13330)
    assert data["rows_per_chip"] == 1495040 >= 1186239 + 306782
    assert cfg["lam"] == pytest.approx(1.0 / (4 * data["block_rows"]))
    assert set(cfg["reduced"]) == {"labels_held"} and "14 DiSMEC batches" in cfg["reduced"]["labels_held"]
    assert set(cfg["guarantees"]) == {"sync", "l2", "columns"}
    tol = cfg["tolerance"]
    assert tol["kink_guard"] == 0 and 0 < tol["step_rel"] < 1e-2
    assert 0 < tol["eval_loss_abs"] and 0 < tol["eval_acc_abs"]


def test_the_roofline_reader_takes_the_cells_engine_record():
    """`row_step_roofline` reads this cell's shapes as it reads the topics
    cell's; a list's 32 B a row over 1,000 outputs are under one byte a
    pair and the accepted count floors them to 0."""
    step = {"seconds": 1e-3, "steps": 100}
    run = SimpleNamespace(
        trace={"worst_device": "d", "devices": {"d": {"program": {"step": step}}}},
        ctx=SimpleNamespace(peaks={"bf16_flops": 197e12, "hbm_bps": 819e9}),
        engine={"batch_size": 100, "virtual_workers": 4, "row_width": 72, "n_outputs": 1000,
                "n_features": 203882, "label_bytes": 0.032, "dense": False})
    share = row_step_roofline.read(run)
    assert 16.0 < share < 18.0  # 138.8 MB = 169.5 us of a 1 ms step


def test_the_cell_rehearses_on_the_cpu_without_a_fault():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seed", "3600000077", "--rehearse"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    last = done.stdout.strip().splitlines()[-1]
    assert last.startswith("rehearsal: ")
    said = json.loads(last[len("rehearsal: "):])
    assert said["failed"] == 0 and said["attempted"] >= 1
    assert said["would_report"] == ["setup_s", "train_samples_per_s"]

    def printed(label):
        return next(json.loads(line[len(label) + 2:]) for line in done.stdout.splitlines()
                    if line.startswith(label + ": "))

    # every check but the quality band, which is the chip's at full size (a
    # rehearsal's 16,384 rows end far above it, as every cell's do)
    checks = printed("checks")
    step, evaluation = checks["step_vs_reference"], checks["evaluation_vs_reference"]
    assert step["update_rel_err"] <= step["tol"] and step["outputs"] == 1000
    assert evaluation["loss_abs_err"] <= evaluation["loss_tol"]
    assert evaluation["acc_abs_err"] <= evaluation["acc_tol"]
    assert checks["finite"] and checks["guarantees"]["weights"] == [203882, 1000]
    assert checks["guarantees"]["workers"] == checks["guarantees"]["node_count"] == 4
    engine = printed("engine")
    assert (engine["kernel"], engine["update"], engine["labels"]) == ("gather", "sparse", "lists")
    assert (engine["n_outputs"], engine["row_width"], engine["n_features"]) == (1000, 72, 203882)
