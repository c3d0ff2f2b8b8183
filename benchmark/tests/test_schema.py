"""Every file of the benchmark loads, every name and unit uses only the
characters the contract permits, and a later PR can add a configuration, a
traffic mix, a cell and a per-layer metric as new files plus entries,
editing none that is there."""

import glob
import json
import os
import shutil

import pytest

from benchmark import harness

ROOT = harness.ROOT


def test_benchmark_json_loads_and_keeps_the_contract():
    bench = harness.load_benchmark(ROOT)
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    assert bench["paths"] == ["benchmark"]
    names = lambda kind: [e["name"] for e in bench[kind]]  # noqa: E731
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(set(names(kind))) == len(names(kind))
        for n in names(kind):
            assert harness.NAME_RE.match(n)
    assert not set(names("end_to_end")) & set(names("per_layer"))
    assert "setup_s" in names("end_to_end")
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 1 and len(bench["workloads"]) // 4 <= 1
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert harness.UNIT_RE.match(m["unit"]) and len(m["unit"]) <= 16
        assert m["better"] in ("lower", "higher") and m["source"] in harness.SOURCES
        allowed = {"name", "unit", "better", "source", "workloads"}
        allowed |= {"bound"} if m in bench["end_to_end"] else {"layer", "moves"}
        assert set(m) <= allowed
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in names("end_to_end")
    for e in bench["workloads"] + bench["configs"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"] and "\t" not in e["why"]
    for c in bench["configs"]:
        assert 1 <= len(c["source"]) <= 200 and c["file"].startswith("benchmark/")
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert len(json.dumps(bench)) < 64 * 1024


def test_every_cell_loads_and_reports_what_it_must():
    bench = harness.load_benchmark(ROOT)
    for w in bench["workloads"]:
        cell = harness.load_cell(bench, w["name"], ROOT)
        assert cell.chips == w["chips"]
        e2e = [m["name"] for m in harness.metrics_for(bench, "end_to_end", w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = harness.metrics_for(bench, "per_layer", w["name"])
        assert layer
        for m in layer:
            # a per-layer metric is reported only where the metric it moves is
            assert m["moves"] in e2e
            assert callable(harness.layer_reader(m["name"]).read)


def test_every_data_file_is_valid_json_and_named_in_permitted_characters():
    for sub in ("configs", "traffic", "quality"):
        files = glob.glob(os.path.join(ROOT, "benchmark", sub, "*.json"))
        assert files
        for f in files:
            with open(f) as fh:
                assert isinstance(json.load(fh), dict)
            assert harness.NAME_RE.match(os.path.basename(f))
    for f in glob.glob(os.path.join(ROOT, "benchmark", "layer_metrics", "*.py")):
        name = os.path.basename(f)[:-3]
        assert callable(harness.layer_reader(name).read)


def test_the_four_chip_configuration_is_the_one_chip_one_on_four_chips():
    """`rcv1-hinge-4chip` exists because a (configuration, traffic) pair may
    appear once: it may differ from `rcv1-hinge` in where it is deployed and
    in what that makes it assume, and in nothing that is run."""
    one, four = (harness.load_json(os.path.join(ROOT, "benchmark", "configs", n + ".json"))
                 for n in ("rcv1-hinge", "rcv1-hinge-4chip"))
    assert set(one) == set(four)
    for key in one:
        if key not in ("deployment", "assumed"):
            assert one[key] == four[key], key
    assert one["deployment"] != four["deployment"]
    extra = set(four["assumed"]) - set(one["assumed"])
    assert extra == {"four_chips"}
    assert all(one["assumed"][k] == four["assumed"][k] for k in one["assumed"])


def test_every_metric_with_a_workloads_list_names_real_cells():
    bench = harness.load_benchmark(ROOT)
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells


def test_a_bad_name_or_unit_is_refused(tmp_path):
    bench = harness.load_benchmark(ROOT)
    for kind, key, bad in (("workloads", "name", "has space"),
                           ("per_layer", "unit", "tokens per second"),
                           ("end_to_end", "unit", "µs")):
        broken = json.loads(json.dumps(bench))
        broken[kind][0][key] = bad
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(broken))
        with pytest.raises(harness.BenchmarkError):
            harness.load_benchmark(str(tmp_path))


def test_a_later_pr_adds_files_and_entries_and_edits_none(tmp_path, monkeypatch):
    """A throw-away configuration, traffic mix, quality band, cell and
    per-layer metric, added to a copy of the tree: nothing that was there
    changes, and the harness finds all of them by name."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns(".trace", ".cache", "__pycache__", "tests"))
    before = {p: open(p, "rb").read()
              for p in glob.glob(str(root / "benchmark" / "**" / "*.*"), recursive=True)
              if os.path.isfile(p)}
    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())

    cfg = json.loads((root / "benchmark/configs/rcv1-hinge.json").read_text())
    cfg["model"] = "logistic"
    (root / "benchmark/configs/rcv1-logistic.json").write_text(json.dumps(cfg))
    traffic = json.loads((root / "benchmark/traffic/sync-4w-b100.json").read_text())
    traffic["batch_size"] = 1024
    (root / "benchmark/traffic/sync-4w-b1024.json").write_text(json.dumps(traffic))
    (root / "benchmark/quality/rcv1-logistic-b1024.json").write_text(
        json.dumps({"budget_epochs": 3, "loss_band": [0.1, 0.2]}))
    (root / "benchmark/layer_metrics/epochs_in_window.py").write_text(
        "def read(run):\n    return float(len(run.periods)) or None\n")
    bench["configs"].append({"name": "rcv1-logistic", "source": "throw-away",
                             "file": "benchmark/configs/rcv1-logistic.json",
                             "reduced": ["rows"], "why": "a test"})
    bench["workloads"].append({"name": "rcv1-logistic-b1024", "config": "rcv1-logistic",
                               "traffic": "sync-4w-b1024", "chips": 1, "why": "a test"})
    bench["per_layer"].append({"name": "epochs_in_window", "unit": "count",
                               "better": "higher", "source": "program_span",
                               "layer": "fit loop", "moves": "train_samples_per_s",
                               "workloads": ["rcv1-logistic-b1024"]})
    bench["end_to_end"][0]["workloads"].append("rcv1-logistic-b1024")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    loaded = harness.load_benchmark(str(root))
    cell = harness.load_cell(loaded, "rcv1-logistic-b1024", str(root))
    assert cell.config["model"] == "logistic" and cell.traffic["batch_size"] == 1024
    assert cell.quality["loss_band"] == [0.1, 0.2]
    layer = [m["name"] for m in harness.metrics_for(loaded, "per_layer", cell.name)]
    assert "epochs_in_window" in layer and "allreduce_us_per_step" not in layer
    # the reader is found by name from the checkout it was added to
    monkeypatch.syspath_prepend(str(root))
    import importlib
    import sys

    saved = {k: sys.modules.pop(k) for k in list(sys.modules)
             if k == "benchmark" or k.startswith("benchmark.")}
    try:
        reader = importlib.import_module("benchmark.layer_metrics.epochs_in_window")

        class _Run:
            periods = [1, 2, 3]

        assert reader.read(_Run()) == 3.0
    finally:
        for k in [k for k in sys.modules if k == "benchmark" or k.startswith("benchmark.")]:
            del sys.modules[k]
        sys.modules.update(saved)
    for p, content in before.items():
        assert open(p, "rb").read() == content
