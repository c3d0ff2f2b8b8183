"""One span, three sinks (utils/measure.py), the `jax.named_scope` names of
the compiled programs, the spans of the two fit loops, and which function
compiled (compile_cache.py).

The contracts under test:

- a `measure.span` opened inside a `jax.profiler` session is an event of
  `/host:CPU` in the `.xplane.pb`, with its name and arguments, nested by
  time under the span the thread already holds;
- with no session, no DSGD_TRACE and `histogram=False` a span allocates no
  trace `Span`, no `TraceAnnotation` and records no histogram, inside the
  off-cost budget of PERF.md (generous factor: a loaded CI host);
- the lowered programs carry every `dsgd.*` scope in `op_name`, and the
  jitted functions keep the names the benchmark finds them by;
- `SyncTrainer.fit` and `HogwildEngine.fit` open every span of their loops
  with `epoch=` / `worker=` / `dispatch=`; `DSGD_PROFILE_DIR` traces one
  steady period;
- `BoundSync.evaluate`'s four phases tile the caller's span in order, and
  what it returns is the formula of before the phases, bit for bit; the
  evaluation programs name their pieces (`dsgd.eval_rows`, `dsgd.margins`,
  `dsgd.eval_reduce`) and the epoch program's scopes are what they were;
- `SPAN_NAME_ALLOWLIST` holds exactly the names opened.
"""

import glob
import logging
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sgd_tpu import compile_cache
from distributed_sgd_tpu.core.trainer import SyncTrainer
from distributed_sgd_tpu.data.rcv1 import Dataset, dim_sparsity, train_test_split
from distributed_sgd_tpu.data.synthetic import dense_regression, rcv1_like
from distributed_sgd_tpu.models.linear import make_model
from distributed_sgd_tpu.parallel.hogwild import HogwildEngine, _Worker
from distributed_sgd_tpu.parallel.mesh import make_mesh
from distributed_sgd_tpu.parallel.sync import SyncEngine
from distributed_sgd_tpu.trace import Span
from distributed_sgd_tpu.utils import measure
from distributed_sgd_tpu.utils import metrics as metrics_mod
from distributed_sgd_tpu.utils.metrics import Metrics

PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(measure.__file__)))

STEP_SCOPES = {"dsgd.draw", "dsgd.margins", "dsgd.coeff", "dsgd.scatter",
               "dsgd.regularize", "dsgd.update"}
# the phases of `BoundSync.evaluate`, in the order it opens them
EVALUATE_PHASES = ("trainer.evaluate.dispatch", "trainer.evaluate.wait",
                   "trainer.evaluate.pull", "trainer.evaluate.reg")
SYNC_FIT_SPANS = {"trainer.epoch", "trainer.evaluate", *EVALUATE_PHASES,
                  "trainer.bookkeeping", "trainer.criterion"}
WORKER_PHASES = {"slave.async.drain", "slave.async.step", "slave.async.apply",
                 "slave.async.pull", "slave.async.push"}


# -- reading a trace back -------------------------------------------------------


def _host_spans(directory, names):
    """{n: [(start, end, name, {stat: value})]}, one entry per thread, of the `/host:CPU`
    events whose name is in `names`, from the newest trace under `directory`."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        str(directory), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            found = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, dict(ev.stats))
                     for ev in line.events if ev.name in names]
            if found:
                out[len(out)] = sorted(found, key=lambda e: e[:2])
    return out


def _flat(spans):
    return [e for line in spans.values() for e in line]


def _inside(child, parent) -> bool:
    return parent[0] <= child[0] and child[1] <= parent[1]


def _sparse_problem(rows=512, d=1000):
    data = rcv1_like(rows, n_features=d, nnz=8, noise=0.0, seed=3)
    return data, make_model("hinge", 1e-5, d, dim_sparsity=dim_sparsity(data))


# -- sink three: the profiler ------------------------------------------------------


def test_span_in_a_profiler_session_is_in_the_xplane_with_args_and_parent(tmp_path):
    m = Metrics()
    with jax.profiler.trace(str(tmp_path)):
        with measure.span("trainer.epoch", metrics=m, epoch=3):
            with measure.span("trainer.evaluate", metrics=m, epoch=3, split="train"):
                with measure.span("trainer.evaluate.pull", histogram=False, root=False):
                    time.sleep(0.002)
    spans = _host_spans(tmp_path, {"trainer.epoch", "trainer.evaluate",
                                   "trainer.evaluate.pull"})
    assert len(spans) == 1  # all on the calling thread's line
    (epoch, evaluate, pull), = spans.values()
    assert [e[2] for e in (epoch, evaluate, pull)] == [
        "trainer.epoch", "trainer.evaluate", "trainer.evaluate.pull"]
    assert _inside(evaluate, epoch) and _inside(pull, evaluate)
    assert pull[1] - pull[0] >= 2_000_000  # the sleep, in nanoseconds
    assert int(epoch[3]["epoch"]) == 3
    assert int(evaluate[3]["epoch"]) == 3 and evaluate[3]["split"] == "train"
    # the histogram sink is fed by the same spans
    assert m.histogram("span.trainer.epoch").count == 1
    assert m.histogram("span.trainer.evaluate").count == 1
    assert "span.trainer.evaluate.pull" not in m._hists


def test_off_path_allocates_no_span_and_records_no_histogram(monkeypatch):
    def _boom(*a, **k):
        raise AssertionError("allocated on the everything-off path")

    assert not jax.profiler.TraceAnnotation.is_enabled()
    with measure.span("trainer.epoch"):
        pass  # binds the lazily imported annotation class
    monkeypatch.setattr(Span, "__init__", _boom)
    monkeypatch.setattr(measure, "_TraceAnnotation", type(
        "Poisoned", (), {"is_enabled": staticmethod(lambda: False),
                         "__init__": _boom}))
    m = Metrics()
    with measure.span("slave.async.pull", metrics=m, histogram=False,
                      worker=1, dispatch=7) as s:
        s.event("ignored")  # the no-op trace span
    assert m._hists == {}
    with measure.span("slave.async.iteration", metrics=m, worker=1, dispatch=7):
        pass
    assert set(m._hists) == {"span.slave.async.iteration"}


def _cost_us(n, **kw):
    m = Metrics()
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for i in range(n):
            with measure.span("slave.async.iteration", metrics=m, worker=1,
                              dispatch=i, **kw):
                pass
        best = min(best, (time.perf_counter() - t0) / n * 1e6)
    return best


def test_off_cost_stays_inside_the_budget():
    """PERF.md's budget is 1.5 us (`histogram=False`) and 5 us (full) in a
    quiet loop of 1e5; ten times that here, where five other test workers
    share the cores.  Six phase spans of a Hogwild dispatch: under 15 us."""
    assert _cost_us(20_000, histogram=False) < 15.0
    assert _cost_us(20_000) < 50.0


def test_span_names_keep_clear_of_the_benchmarks_prefixes():
    assert not [n for n in measure.SPAN_NAME_ALLOWLIST
                if n.startswith(("bench.", "$"))]


def test_allowlist_holds_exactly_the_names_opened():
    opened = set()
    pat = re.compile(r"(?:measure\.|(?<![\w.]))span\(\s*[\"']([\w.]+)[\"']")
    for path in glob.glob(os.path.join(PACKAGE, "**", "*.py"), recursive=True):
        if path.endswith(os.path.join("utils", "measure.py")):
            continue
        with open(path) as f:
            opened.update(pat.findall(f.read()))
    assert opened == set(measure.SPAN_NAME_ALLOWLIST)


# -- the scopes of the compiled programs ----------------------------------------------


def _scopes(lowered) -> set:
    text = lowered.as_text(dialect="hlo", debug_info=True)
    return {s for op in re.findall(r'op_name="([^"]*)"', text)
            for s in re.findall(r"dsgd\.[a-z_]+", op)}


def _bound(kernel, virtual_workers, n_devices, n_outputs=1, labels=False):
    """`labels`: dense rows with class labels (the regression's evaluation
    counts no hit and its `predict` is the margins themselves)."""
    if n_outputs > 1:  # an output axis: `l2`, a row of labels a sample
        data = rcv1_like(512, n_features=1000, nnz=8, noise=0.0, seed=3, n_outputs=n_outputs)
        model = make_model("hinge", 1e-5, 1000, regularizer="l2", n_outputs=n_outputs)
    elif kernel == "dense" and labels:
        sparse = rcv1_like(512, n_features=64, nnz=8, noise=0.0, seed=3)
        x = np.zeros((512, 64), np.float32)
        np.add.at(x, (np.arange(512)[:, None], sparse.indices), sparse.values)
        data = Dataset.dense(x, sparse.labels)
        model = make_model("logistic", 1e-5, 64, regularizer="l2")
    elif kernel == "dense":
        data = dense_regression(512, 64)
        model = make_model("least_squares", 1e-5, 64)
    else:
        data, model = _sparse_problem()
    engine = SyncEngine(model, make_mesh(n_devices), 16, 0.5, kernel=kernel,
                        virtual_workers=virtual_workers)
    shape = (model.n_features,) + ((n_outputs,) if n_outputs > 1 else ())
    return engine.bind(data), jnp.zeros(shape, jnp.float32)


@pytest.mark.parametrize("kernel,virtual_workers,n_devices", [
    ("mxu", 4, 1), ("mxu", 1, 4), ("dense", 4, 1), ("scalar", 4, 1)])
def test_epoch_and_eval_programs_carry_every_scope(kernel, virtual_workers, n_devices):
    b, w = _bound(kernel, virtual_workers, n_devices)
    epoch = b._epoch.lower(w, b._opt_state, b.data.indices, b.data.values,
                           b.data.labels, jax.random.PRNGKey(0))
    want = STEP_SCOPES | {"dsgd.allreduce"}
    if kernel == "mxu":
        want |= {"dsgd.onehot", "dsgd.layout"}
    assert _scopes(epoch) == want
    evaluation = b._eval.lower(w, b.data.indices, b.data.values, b.data.labels)
    assert {"dsgd.eval", "dsgd.margins", "dsgd.allreduce"} <= _scopes(evaluation)
    # a margin of the evaluation is nested under dsgd.eval, not beside it
    # (the compiler joins a called body's relative path to its caller's)
    assert re.search(r'op_name="[^"]*dsgd\.eval/[^"]*dsgd\.margins',
                     evaluation.compile().as_text())
    # the benchmark finds the programs by these names on `XLA Modules`
    assert "jit__epoch_shard" in epoch.as_text().split("\n")[0]
    assert "jit__eval_shard" in evaluation.as_text().split("\n")[0]


@pytest.mark.parametrize("kernel,n_outputs", [
    ("mxu", 1), ("gather", 1), ("dense", 1), ("gather", 3)])
def test_evaluation_programs_name_their_pieces_and_the_epochs_scopes_stay(kernel, n_outputs):
    """The benchmark's boundary metrics read `dsgd.eval_rows` (a chunk's
    fetch), `dsgd.margins` / `dsgd.onehot` (the model's own) and
    `dsgd.eval_reduce` (losses, hits, sums) inside `jit__eval_shard`; no
    scope moves inside the epoch program, so every `*_us_per_step` reads
    what it read."""
    b, w = _bound(kernel, 4, 1, n_outputs=n_outputs, labels=True)
    evaluation = b._eval.lower(w, b.data.indices, b.data.values, b.data.labels)
    prediction = b._predict.lower(w, b.data.indices, b.data.values)
    for lowered in (evaluation, prediction):
        assert {"dsgd.eval", "dsgd.eval_rows", "dsgd.eval_reduce",
                "dsgd.margins"} <= _scopes(lowered)
        ops = re.findall(r'op_name="([^"]*)"', lowered.as_text(dialect="hlo", debug_info=True))
        innermost = {found[-1] for op in ops if (found := re.findall(r"dsgd\.[a-z_]+", op))}
        # the margins keep their own name innermost: no piece wraps another
        assert {"dsgd.eval_rows", "dsgd.margins", "dsgd.eval_reduce"} <= innermost
        assert not [op for op in ops if re.search(
            r"dsgd\.(margins|onehot)/.*dsgd\.eval_(rows|reduce)"
            r"|dsgd\.eval_(rows|reduce)/.*dsgd\.(margins|onehot|eval_)", op)]
        # the chunk's fetch is under eval_rows, the sums under eval_reduce
        assert [op for op in ops if re.search(r"dsgd\.eval_rows/dynamic_slice", op)]
        assert [op for op in ops if re.search(r"dsgd\.eval_reduce/", op)]
        # and all of them under dsgd.eval, not beside it (the compiler joins a
        # called body's relative path to its caller's)
        assert re.search(r'op_name="[^"]*dsgd\.eval/[^"]*dsgd\.eval_(rows|reduce)',
                         lowered.compile().as_text())
    assert "dsgd.allreduce" in _scopes(evaluation)
    assert "jit__eval_shard" in evaluation.as_text().split("\n")[0]
    # the epoch program: the step's scopes and nothing of the evaluation's
    epoch = b._epoch.lower(w, b._opt_state, b.data.indices, b.data.values,
                           b.data.labels, jax.random.PRNGKey(0))
    want = STEP_SCOPES | {"dsgd.allreduce"}
    if kernel == "mxu":
        want |= {"dsgd.onehot", "dsgd.layout"}
    if kernel == "gather":
        want |= {"dsgd.layout"}
    assert _scopes(epoch) == want


def test_the_one_hot_evaluations_second_fetch_is_eval_rows_too():
    """A chunk of more than `mxu.MATVEC_SUB` rows goes through the margins'
    sub-scan, which slices it again (on the TPU: the `[512,76]` re-layout
    copies): those slices are the evaluation's row fetch by name, the
    matmul under them is still the margins', and no step comes this way."""
    from distributed_sgd_tpu.ops import mxu

    data = rcv1_like(4 * mxu.MATVEC_SUB, n_features=1000, nnz=8, noise=0.0, seed=3)
    model = make_model("hinge", 1e-5, 1000, dim_sparsity=dim_sparsity(data))
    b = SyncEngine(model, make_mesh(1), 16, 0.5, kernel="mxu", virtual_workers=4,
                   eval_chunk=2 * mxu.MATVEC_SUB).bind(data)
    w = jnp.zeros((1000,), jnp.float32)
    text = b._eval.lower(w, b.data.indices, b.data.values, b.data.labels).as_text(
        dialect="hlo", debug_info=True)
    sliced = re.findall(r'(\w+\[[\d,]*\])\S* dynamic-slice\([^\n]*op_name="([^"]*)"', text)
    rows = f"[{mxu.MATVEC_SUB},8]"
    assert {op for shape, op in sliced if shape.endswith(rows)} == {
        "dsgd.eval_rows/dynamic_slice"}
    assert len([1 for shape, _op in sliced if shape.endswith(rows)]) == 2  # indices, values
    epoch = b._epoch.lower(w, b._opt_state, b.data.indices, b.data.values,
                           b.data.labels, jax.random.PRNGKey(0))
    assert "dsgd.eval_rows" not in _scopes(epoch)


@pytest.mark.parametrize("blocked", [False, True])
def test_hogwild_kstep_carries_every_scope_and_keeps_its_name(blocked, monkeypatch):
    from distributed_sgd_tpu.ops import mxu

    monkeypatch.setattr(mxu, "blocked_pays_off", lambda device=None: blocked)
    data, model = _sparse_problem()
    worker = _Worker(0, model, data, jax.devices()[0], 16, 0.5, 0, Metrics(),
                     steps_per_dispatch=2)
    assert bool(worker._blocked) == blocked
    w = jnp.zeros((model.n_features,), jnp.float32)
    kstep = worker._step.lower(w, None, worker._idx, worker._val, worker._y,
                               jax.random.PRNGKey(0))
    want = STEP_SCOPES | ({"dsgd.onehot"} if blocked else set())
    assert want <= _scopes(kstep)
    assert "jit_kstep" in kstep.as_text().split("\n")[0]


# -- the spans of the two fit loops -----------------------------------------------------


def _sync_fit(profile_dir, max_epochs, criterion=None):
    data, model = _sparse_problem()
    train, test = train_test_split(data)
    trainer = SyncTrainer(model, make_mesh(2), batch_size=16, learning_rate=0.5,
                          metrics=Metrics(), profile_dir=str(profile_dir))
    result = trainer.fit(train, test, max_epochs=max_epochs, criterion=criterion)
    return trainer, result


def test_sync_fit_yields_every_span_with_its_epoch(tmp_path):
    """Through `profile_dir` (DSGD_PROFILE_DIR): the trace holds the period
    of epoch start+2, the first whose programs have all run twice."""
    trainer, result = _sync_fit(tmp_path, 5, criterion=lambda losses: False)
    assert result.epochs_run == 5
    spans = _host_spans(tmp_path, SYNC_FIT_SPANS)
    assert len(spans) == 1
    (line,) = spans.values()
    assert {e[2] for e in line} == SYNC_FIT_SPANS
    by_name = {}
    for e in line:
        by_name.setdefault(e[2], []).append(e)
    # one whole period, and only one: epoch 2's
    assert [int(e[3]["epoch"]) for e in by_name["trainer.epoch"]] == [2]
    assert [(int(e[3]["epoch"]), e[3]["split"]) for e in by_name["trainer.evaluate"]] \
        == [(2, "train"), (2, "test")]
    for name in ("trainer.bookkeeping", "trainer.criterion"):
        assert [int(e[3]["epoch"]) for e in by_name[name]] == [2]
    # each evaluation holds its four phases, in order (the test split's
    # dispatch and wait too, though its program is enqueued and pulled with
    # the train split's)
    for phase in EVALUATE_PHASES:
        assert len(by_name[phase]) == 2
        for child, parent in zip(by_name[phase], by_name["trainer.evaluate"]):
            assert _inside(child, parent)
    for parent in by_name["trainer.evaluate"]:
        inside = sorted(e for e in line if e[2] in EVALUATE_PHASES and _inside(e, parent))
        assert [e[2] for e in inside] == list(EVALUATE_PHASES)
    # the phases feed no histogram; the epoch is filed once, under its own name
    hists = trainer.metrics._hists
    assert hists["span.trainer.epoch"].count == 5
    assert hists["span.trainer.evaluate"].count == 10
    assert not [h for h in hists if h.startswith("span.trainer.evaluate.")]
    assert "master.sync.batch.duration" not in hists
    assert hists["master.sync.epoch.seconds"].count == 5


def test_a_short_sync_fit_profiles_its_last_epoch(tmp_path):
    _trainer, result = _sync_fit(tmp_path, 2)
    assert result.epochs_run == 2
    spans = _flat(_host_spans(tmp_path, {"trainer.epoch", "trainer.evaluate"}))
    assert sorted((e[2], int(e[3]["epoch"])) for e in spans) == [
        ("trainer.epoch", 1), ("trainer.evaluate", 1), ("trainer.evaluate", 1)]


def _phase_work(monkeypatch):
    """[(split, phase, call)] of what the fit does inside each phase span of
    `trainer.evaluate`: the evaluation programs' dispatch (by the split of
    the binding), the wait on the weights, the pull, the host's `read`."""
    from distributed_sgd_tpu.parallel import sync

    open_spans, work = [], []
    enter, exit_ = measure.span.__enter__, measure.span.__exit__

    def spy_enter(self):
        open_spans.append((self.name, self._args.get("split")))
        return enter(self)

    def spy_exit(self, *a):
        open_spans.pop()
        return exit_(self, *a)

    def at(call):
        if open_spans and open_spans[-1][0] in EVALUATE_PHASES:
            split = next(s for n, s in reversed(open_spans) if n == "trainer.evaluate")
            work.append((split, open_spans[-1][0], call))

    def spy(name, fn, label=lambda self, *a: None):
        def called(*a, **k):
            at((name, label(*a)))
            return fn(*a, **k)
        return called

    splits = {}
    bind = sync.SyncEngine.bind

    def spy_bind(self, data, *a, **k):
        b = bind(self, data, *a, **k)
        splits[b] = "train" if not splits else "test"
        return b

    monkeypatch.setattr(measure.span, "__enter__", spy_enter)
    monkeypatch.setattr(measure.span, "__exit__", spy_exit)
    monkeypatch.setattr(sync.SyncEngine, "bind", spy_bind)
    monkeypatch.setattr(sync.BoundSync, "dispatch_evaluation", spy(
        "dispatch_evaluation", sync.BoundSync.dispatch_evaluation, lambda b, w: splits[b]))
    monkeypatch.setattr(jax, "block_until_ready", spy("block_until_ready", jax.block_until_ready))
    monkeypatch.setattr(jax, "device_get", spy("device_get", jax.device_get))
    return work


def test_the_four_phases_tile_an_evaluation_in_order(tmp_path, monkeypatch):
    """dispatch, wait, pull, reg: one after the other inside `trainer.evaluate`,
    none overlapping, nothing of the call outside them but the spans' own
    entries and exits (so the device's idle time inside an evaluation is the
    four phases' and the benchmark's split of it sums to the whole).  The
    epoch boundary is one queue: the train split's dispatch enqueues both
    splits' evaluation programs, its wait waits for the epoch program and
    pulls both outputs once; the test split's dispatch and wait hold nothing
    (its program went with the train split's), and each split's pull and reg
    take its own numbers apart on the host."""
    work = _phase_work(monkeypatch)
    _sync_fit(tmp_path, 5, criterion=lambda losses: False)
    per_epoch = [("train", "trainer.evaluate.dispatch", ("dispatch_evaluation", "train")),
                 ("train", "trainer.evaluate.dispatch", ("dispatch_evaluation", "test")),
                 ("train", "trainer.evaluate.wait", ("block_until_ready", None)),
                 ("train", "trainer.evaluate.wait", ("device_get", None))]
    assert work == per_epoch * 5
    (line,) = _host_spans(tmp_path, {"trainer.evaluate", *EVALUATE_PHASES}).values()
    evaluations = [e for e in line if e[2] == "trainer.evaluate"]
    assert len(evaluations) == 2
    for parent in evaluations:
        phases = [e for e in line if e[2] != "trainer.evaluate" and _inside(e, parent)]
        assert [e[2] for e in phases] == list(EVALUATE_PHASES)
        for before, after in zip(phases, phases[1:]):
            assert before[1] <= after[0]
        covered = sum(e[1] - e[0] for e in phases)
        # what lies between them is python entering and leaving eight annotations
        assert parent[1] - parent[0] - covered < 2_000_000


@pytest.mark.parametrize("kernel,n_outputs", [
    ("mxu", 1), ("gather", 1), ("dense", 1), ("gather", 3)])
def test_evaluate_returns_the_formula_of_before_the_phases_bit_for_bit(kernel, n_outputs):
    """The formula of before the phases: the program's two sums as
    `float()` pulled them, bit for bit, beside `lam*||w||^2`, which the
    program now sums itself (to 1e-6 of the eager one); one dispatch and
    one pull (the wait)."""
    b, _w = _bound(kernel, 4, 1, n_outputs=n_outputs, labels=True)
    shape = (b.model.n_features,) + ((n_outputs,) if n_outputs > 1 else ())
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(7), shape, jnp.float32)
    sums = b._eval(w, b.data.indices, b.data.values, b.data.labels)
    loss_sum, hit_sum = float(sums[0]), float(sums[1])
    n = b.data.n_true
    reg = b.model.lam * float(jnp.sum(jnp.asarray(w, jnp.float32) ** 2))
    penalty = b.read(np.asarray(sums)).penalty
    assert penalty == pytest.approx(reg, rel=1e-6)
    assert b.evaluate(w) == (penalty + loss_sum / n, hit_sum / (n * b.model.n_outputs))
    assert 0.0 < hit_sum < n * b.model.n_outputs and loss_sum > 0.0  # a problem, not zeros


def test_hogwild_fit_yields_every_span_with_worker_and_dispatch(tmp_path):
    data = rcv1_like(320, n_features=128, nnz=8, noise=0.0, seed=20)
    train, test = train_test_split(data)
    model = make_model("logistic", 1e-5, 128, regularizer="l2")
    metrics = Metrics()
    engine = HogwildEngine(model, n_workers=2, batch_size=8, learning_rate=0.05,
                           check_every=40, leaky_loss=0.9, backoff_s=0.01, seed=0,
                           steps_per_dispatch=2, metrics=metrics)
    with jax.profiler.trace(str(tmp_path)):
        result = engine.fit(train, test, max_epochs=2)
    assert result.state.updates > 0
    names = WORKER_PHASES | {"slave.async.iteration", "master.async.check",
                             *EVALUATE_PHASES}
    spans = _host_spans(tmp_path, names)
    flat = _flat(spans)
    assert {e[2] for e in flat} == names
    iterations = [e for e in flat if e[2] == "slave.async.iteration"]
    assert {int(e[3]["worker"]) for e in iterations} == {0, 1}
    for worker in (0, 1):
        numbers = [int(e[3]["dispatch"]) for e in iterations if int(e[3]["worker"]) == worker]
        assert numbers == list(range(numbers[0], numbers[0] + len(numbers)))
    # a worker's phases lie inside its iteration, under the same numbers
    for line in spans.values():
        mine = [e for e in line if e[2] == "slave.async.iteration"]
        for e in line:
            if e[2] in WORKER_PHASES:
                parent = next(p for p in mine if _inside(e, p))
                assert (e[3]["worker"], e[3]["dispatch"]) == (
                    parent[3]["worker"], parent[3]["dispatch"])
    checks = [e for e in flat if e[2] == "master.async.check"]
    assert all(int(e[3]["updates"]) >= 0 for e in checks)
    for phase in EVALUATE_PHASES:  # the shared evaluation's, inside a check
        assert [e for e in flat if e[2] == phase and any(_inside(e, c) for c in checks)]
    # only the iteration and the check are filed as histograms
    span_hists = {h for h in metrics._hists if h.startswith("span.")}
    assert span_hists == {"span.slave.async.iteration", "span.master.async.check"}
    dispatches = metrics.counter("slave.async.batch").value // 2
    assert metrics.histogram("span.slave.async.iteration").count == dispatches


# -- which function compiled, and for how long ----------------------------------------------


@pytest.fixture
def compile_listener(monkeypatch):
    """A fresh listener of `compile_cache` on a Metrics of its own, taken
    off jax's monitoring again when the test ends."""
    from jax._src import monitoring

    before = (list(monitoring.get_event_listeners()),
              list(monitoring.get_event_duration_listeners()))
    monkeypatch.setattr(compile_cache, "_listener_installed", False)
    monkeypatch.setattr(compile_cache, "_compiles", [])
    m = Metrics()
    compile_cache._install_listener(m)
    yield m
    for listener in monitoring.get_event_listeners():
        if listener not in before[0]:
            monitoring.unregister_event_listener(listener)
    for listener in monitoring.get_event_duration_listeners():
        if listener not in before[1]:
            monitoring.unregister_event_duration_listener(listener)


def test_compile_listener_names_the_function_and_its_seconds(compile_listener, caplog):
    @jax.jit
    def a_function_nothing_else_compiles(x):
        return x * 3 + 1

    with caplog.at_level(logging.INFO, logger="dsgd.compile"):
        a_function_nothing_else_compiles(jnp.ones(7)).block_until_ready()
    mine = [c for c in compile_cache.compiles()
            if c[1] == "jit(a_function_nothing_else_compiles)"]
    assert len(mine) == 1
    at, _fun, seconds, hit = mine[0]
    assert 0 < seconds < 60 and at <= time.perf_counter() and hit in (False, True)
    assert (compile_listener.histogram(metrics_mod.COMPILE_SECONDS).count
            == len(compile_cache.compiles()))
    assert [r for r in caplog.records if r.name == "dsgd.compile"
            and "a_function_nothing_else_compiles" in r.getMessage()]


def test_compile_log_is_capped_and_tells_a_hit_from_a_miss(compile_listener, monkeypatch):
    from jax._src import monitoring

    monkeypatch.setattr(compile_cache, "MAX_COMPILES", 3)
    backend = "/jax/core/compile/backend_compile_duration"
    monitoring.record_event_duration_secs(backend, 0.25, fun_name="jit(f)")
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event_duration_secs(backend, 0.01, fun_name="jit(g)")
    for _ in range(3):
        monitoring.record_event_duration_secs(backend, 0.25, fun_name="jit(f)")
    # tracing and lowering are not compiles
    monitoring.record_event_duration_secs(
        "/jax/core/compile/jaxpr_trace_duration", 0.25, fun_name="f")
    assert [(c[1], c[2], c[3]) for c in compile_cache.compiles()] == [
        ("jit(f)", 0.25, False), ("jit(g)", 0.01, True), ("jit(f)", 0.25, False)]
    assert compile_listener.histogram(metrics_mod.COMPILE_SECONDS).count == 5
    assert compile_listener.counter(metrics_mod.COMPILE_CACHE_HITS).value == 1
