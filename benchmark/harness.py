"""What every driver shares: loading a cell's files, the device check, the
run record the metric readers get, log capture, and the profiler session.

A cell is data.  `BENCHMARK.json` names a configuration and a traffic mix;
the configuration is `benchmark/configs/<name>.json` and names a generator
`benchmark/gen/<generator>.py`; the traffic mix is
`benchmark/traffic/<name>.json` and names a driver
`benchmark/drivers/<engine>.py`; the cell's quality band is
`benchmark/quality/<cell>.json`; a per-layer metric is a reader
`benchmark/layer_metrics/<name>.py`.  Nothing here or in `run.py` knows a
cell, a configuration or a metric by name.
"""

from __future__ import annotations

import importlib
import json
import logging
import os
import re
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class BenchmarkError(Exception):
    """A file of the benchmark is missing or malformed, or the machine is
    not the one the cell asks for.  `run.py` exits 2 and prints no result."""


def load_json(path: str) -> dict:
    try:
        with open(path, "r") as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise BenchmarkError(f"cannot read {path}: {e}") from e


def _need(d: dict, keys, where: str) -> None:
    missing = [k for k in keys if k not in d]
    if missing:
        raise BenchmarkError(f"{where}: missing {missing}")


def load_benchmark(root: str = ROOT) -> dict:
    """BENCHMARK.json, with every name and unit checked against the
    characters the contract permits."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    _need(bench, ("command", "paths", "run_seconds", "configs", "workloads",
                  "end_to_end", "per_layer"), "BENCHMARK.json")
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for entry in bench[kind]:
            name = entry.get("name", "")
            if not NAME_RE.match(name):
                raise BenchmarkError(f"{kind}: bad name {name!r}")
            if name in seen:
                raise BenchmarkError(f"{kind}: {name!r} appears twice")
            seen.add(name)
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if not UNIT_RE.match(m.get("unit", "")):
                raise BenchmarkError(f"{kind}.{m['name']}: bad unit {m.get('unit')!r}")
            if m.get("better") not in ("lower", "higher"):
                raise BenchmarkError(f"{kind}.{m['name']}: better must be lower|higher")
            if m.get("source") not in SOURCES:
                raise BenchmarkError(f"{kind}.{m['name']}: unknown source")
    for w in bench["workloads"]:
        for key in ("config", "traffic"):
            if not NAME_RE.match(w.get(key, "")):
                raise BenchmarkError(f"workload {w['name']}: bad {key}")
        if w.get("chips") not in (1, 4):
            raise BenchmarkError(f"workload {w['name']}: chips must be 1 or 4")
    return bench


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    quality: dict


def load_cell(bench: dict, workload: str, root: str = ROOT) -> Cell:
    """The cell's entry and its three data files, validated."""
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise BenchmarkError(
            f"no workload {workload!r} in BENCHMARK.json "
            f"(have {[w['name'] for w in bench['workloads']]})")
    cfg_entry = next((c for c in bench["configs"] if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise BenchmarkError(f"workload {workload}: no config {entry['config']!r}")
    config = load_json(os.path.join(root, cfg_entry["file"]))
    _need(config, ("generator", "data", "model", "regularizer", "lam",
                   "learning_rate", "tolerance"), cfg_entry["file"])
    traffic_file = os.path.join(root, "benchmark", "traffic", entry["traffic"] + ".json")
    traffic = load_json(traffic_file)
    _need(traffic, ("engine", "node_count", "batch_size"), traffic_file)
    for kind, mod in (("gen", config["generator"]), ("drivers", traffic["engine"])):
        if not NAME_RE.match(mod) or not os.path.isfile(
                os.path.join(root, "benchmark", kind, mod + ".py")):
            raise BenchmarkError(f"{workload}: no benchmark/{kind}/{mod}.py")
    quality_file = os.path.join(root, "benchmark", "quality", workload + ".json")
    quality = load_json(quality_file)
    _need(quality, ("loss_band",), quality_file)
    return Cell(workload, int(entry["chips"]), entry["config"], config,
                entry["traffic"], traffic, quality)


def metrics_for(bench: dict, kind: str, workload: str) -> List[dict]:
    """The `kind` metrics this cell reports: those without a `workloads`
    list, and those whose list names the cell."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def layer_reader(name: str):
    """The reader module of a per-layer metric."""
    try:
        return importlib.import_module(f"benchmark.layer_metrics.{name}")
    except ImportError as e:
        raise BenchmarkError(f"no reader benchmark/layer_metrics/{name}.py: {e}") from e


# -- the machine ------------------------------------------------------------

def check_devices(chips: int, rehearse: bool):
    """(devices, device dict, peaks row).  Off a TPU, on another number of
    chips than the cell asks for, or on a chip the peak table does not
    list, this raises: a result would be a number of the wrong machine."""
    import jax

    from benchmark import peaks

    devices = jax.devices()
    d0 = devices[0]
    info = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices)}
    if rehearse:
        if len(devices) < chips:
            raise BenchmarkError(f"rehearsal needs {chips} devices, found {len(devices)}")
        return devices[:chips], dict(info, count=chips), None
    if d0.platform != "tpu":
        raise BenchmarkError(f"no accelerator: jax reports platform {d0.platform!r}")
    if len(devices) != chips:
        raise BenchmarkError(f"the cell asks for {chips} chip(s), jax reports {len(devices)}")
    try:
        row = peaks.peaks_for(d0.device_kind)
    except KeyError as e:
        raise BenchmarkError(str(e)) from e
    return devices, info, row


def memory_peak_bytes(devices) -> Optional[int]:
    """Peak bytes in use on the fullest device, as the runtime reports it."""
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


# -- the run record -----------------------------------------------------------

@dataclass
class Context:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_process: float  # perf_counter at the top of run.py
    devices: list
    device: dict
    peaks: Optional[dict]
    trace_dir: str
    setup: Dict[str, float] = field(default_factory=dict)  # the split of setup_s

    def mark(self, name: str, since: float) -> None:
        """Record a piece of set-up that began at `since`."""
        self.setup[name] = time.perf_counter() - since


@dataclass
class Run:
    """What a driver hands back.  Readers of per-layer metrics take what
    they need from it and return None where there is nothing to read."""
    ctx: Context
    correct: bool
    checks: Dict[str, Any]          # each check's numbers, printed on an earlier line
    attempted: int
    failed: int
    end_to_end: Dict[str, float]    # the driver's end-to-end readings by name
    window_start: float             # perf_counter when warm-up ended
    window_seconds: float
    compiles: tuple                 # persistent-cache (hits + misses) at window (start, end)
    periods: List[dict] = field(default_factory=list)    # sync: one per window epoch
    counters: Dict[str, dict] = field(default_factory=dict)  # name -> {start, end, samples}
    engine: Dict[str, Any] = field(default_factory=dict)  # what the program's objects said
    fit: Dict[str, Any] = field(default_factory=dict)     # the fit's own series
    trace_path: Optional[str] = None
    trace_opens_in: Optional[str] = None  # the program the traced window has to open inside
    trace: Optional[dict] = None    # reduce_trace.reduce(...) of the traced window


# -- log capture ----------------------------------------------------------------

class LogTap(logging.Handler):
    """Keeps the program's own log records (logger name, message template,
    arguments) with the host clock at which each arrived."""

    def __init__(self, root_logger: str = "dsgd"):
        super().__init__(level=logging.INFO)
        self.records: List[tuple] = []
        self._logger = logging.getLogger(root_logger)
        self._level = self._logger.level
        self._logger.setLevel(logging.INFO)
        self._logger.addHandler(self)

    def emit(self, record: logging.LogRecord) -> None:
        self.records.append((time.perf_counter(), record.name, record.msg, record.args))

    def all(self, logger: str, prefix: str) -> List[tuple]:
        return [r for r in self.records
                if r[1] == logger and isinstance(r[2], str) and r[2].startswith(prefix)]

    def first(self, logger: str, prefix: str) -> Optional[tuple]:
        return next(iter(self.all(logger, prefix)), None)

    def last(self, logger: str, prefix: str) -> Optional[tuple]:
        return next((r for r in reversed(self.records)
                     if r[1] == logger and isinstance(r[2], str) and r[2].startswith(prefix)),
                    None)

    def close(self) -> None:
        self._logger.removeHandler(self)
        self._logger.setLevel(self._level)
        super().close()


# -- the problem -------------------------------------------------------------------

def build_problem(ctx: Context):
    """(Problem, model): rows from the configuration's generator, on the
    cell's devices, and the program's own `make_model` over them."""
    from distributed_sgd_tpu.models.linear import make_model

    import jax

    cfg = ctx.cell.config
    gen = importlib.import_module(f"benchmark.gen.{cfg['generator']}")
    t0 = time.perf_counter()
    problem = gen.generate(cfg["data"], ctx.seed, ctx.devices, ctx.rehearse)
    jax.block_until_ready((problem.train.values, problem.test.values))
    ctx.mark("rows_s", t0)
    model = make_model(cfg["model"], float(cfg["lam"]), problem.n_features,
                       dim_sparsity=problem.dim_sparsity,
                       regularizer=cfg["regularizer"])
    return problem, model


def problem_facts(problem) -> dict:
    """The problem's shapes, for the run's `engine` record."""
    return {"n_features": problem.n_features,
            "row_width": int(problem.train.values.shape[1]),
            "dense": bool(problem.train.indices.shape[1] == 0),
            "train_rows": len(problem.train), "test_rows": len(problem.test)}


def program_config(ctx: Context):
    """The program's `Config` at its defaults, with only what the
    configuration and the traffic mix fix set on it.  Mechanisms (kernel,
    steps_per_dispatch, check_every ...) stay at the program's defaults, so
    a PR that changes a default shows in the cell."""
    from distributed_sgd_tpu.config import Config

    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    return Config(
        seed=ctx.seed, model=cfg["model"], lam=float(cfg["lam"]),
        learning_rate=float(cfg["learning_rate"]),
        batch_size=int(traffic["batch_size"]),
        node_count=int(traffic["node_count"]),
        use_async=bool(traffic.get("use_async", False)),
    )


# -- the profiler ---------------------------------------------------------------------

class TraceSession:
    """`jax.profiler` started and stopped by the benchmark, in the steady
    window, around whole units of work."""

    def __init__(self, directory: str):
        self.directory = directory
        self.requested_at: Optional[float] = None  # start() called
        self.started_at: Optional[float] = None    # the profiler is recording
        self.stopped_at: Optional[float] = None

    @property
    def running(self) -> bool:
        return self.started_at is not None and self.stopped_at is None

    def start(self) -> None:
        import shutil

        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        self.started_at = self.stopped_at = None  # a second session after a dropped one
        self.requested_at = time.perf_counter()
        jax.profiler.start_trace(self.directory)
        self.started_at = time.perf_counter()

    def stop(self) -> None:
        import jax

        self.stopped_at = time.perf_counter()
        jax.profiler.stop_trace()

    def path(self) -> Optional[str]:
        import glob

        found = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


def seeded_rows(data, n: int, seed: int):
    """(indices, values, labels) of `n` seeded resident rows as host arrays.
    A split sharded over devices is drawn from shard by shard, each on its
    own device: indexing the global array would have XLA gather it whole."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    pieces = sorted(data.values.addressable_shards, key=lambda s: s.index[0].start or 0)
    by_device = {next(iter(s.data.devices())): s.data for s in data.labels.addressable_shards}
    dense = data.indices.shape[1] == 0
    if not dense:
        idx_of = {next(iter(s.data.devices())): s.data for s in data.indices.addressable_shards}
    out = ([], [], [])
    for piece in pieces:
        dev = next(iter(piece.data.devices()))
        rows = piece.data.shape[0]
        ids = jnp.asarray(np.sort(rng.choice(rows, size=min(rows, -(-n // len(pieces))),
                                             replace=False)))
        out[0].append(np.empty((len(ids), 0), np.int32) if dense
                      else np.asarray(idx_of[dev][ids]))
        out[1].append(np.asarray(piece.data[ids]))
        out[2].append(np.asarray(by_device[dev][ids]))
    return tuple(np.concatenate(a)[:n] for a in out)


def rel_err(a, b) -> float:
    """||a - b||_2 / ||b||_2 in float64 on the host."""
    import numpy as np

    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))
