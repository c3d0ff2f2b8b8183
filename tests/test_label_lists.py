"""Labels as ID LISTS and the squared hinge (PERF.md section 6, PR 36): a
one-vs-rest fit over a RANGE of labels, a row's labels the ids of its
positives among the C outputs the fit holds (`Dataset.n_labels`), through
the mesh sync engine.

Three references hold the program: the plain equations of
`benchmark/reference_lists.py` (one step, the evaluation), the program
itself on the same labels as a dense `[N, C]` array (weight for weight),
and the share property DiSMEC rests on (the fits of label ranges, side by
side, ARE the fit of all labels).  C = 200 and 300: two and three lane
groups a weight row.  Small sizes, the CPU.
"""

import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_lists
from distributed_sgd_tpu.core.trainer import SyncTrainer
from distributed_sgd_tpu.data.multilabel import read_multilabel, to_lists
from distributed_sgd_tpu.data.rcv1 import LIST_NO_ROW, LIST_PAD, Dataset
from distributed_sgd_tpu.data.synthetic import rcv1_like
from distributed_sgd_tpu.models.linear import expand_labels, make_model
from distributed_sgd_tpu.ops import gather, kernels
from distributed_sgd_tpu.parallel.mesh import make_mesh
from distributed_sgd_tpu.parallel.sync import SyncEngine
from distributed_sgd_tpu.utils import metrics as metrics_mod

D, N, P = 3000, 512, 6
LAM, LR, BATCH = 1e-3, 0.02, 8
LOSSES = ("hinge", "logistic", "squared_hinge", "least_squares")


def to_dense(lists, n_labels: int) -> np.ndarray:
    """int8[N, n_labels] of +/-1 from lists (a LIST_NO_ROW row: all 0)."""
    lists = np.asarray(lists)
    y = np.full((len(lists), n_labels), -1, np.int8)
    rows, slots = np.nonzero(lists >= 0)
    y[rows, lists[rows, slots]] = 1
    y[lists[:, 0] == LIST_NO_ROW] = 0
    return y


def _dense(n_outputs: int, seed: int = 3) -> Dataset:
    return rcv1_like(N, n_features=D, nnz=P, seed=seed, n_outputs=n_outputs)


def _listed(data: Dataset, first: int = 0, end=None) -> Dataset:
    """`data` with the labels of columns [first, end) as lists."""
    y = data.labels[:, first:end]
    lists, cut = to_lists(y)
    assert cut == 0
    return Dataset(data.indices, data.values, lists, data.n_features, n_labels=y.shape[1])


def _weights(n_outputs: int, seed: int = 5):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(D, n_outputs)) * 0.1,
                       jnp.float32)


def _bind(data, n_outputs, loss="squared_hinge", reg="l2", devices=1, workers=4,
          kernel=kernels.AUTO):
    model = make_model(loss, LAM, D, regularizer=reg, n_outputs=n_outputs)
    return SyncEngine(model, make_mesh(devices), BATCH, LR, eval_chunk=64,
                      kernel=kernel, virtual_workers=workers).bind(data)


def _batches(bound, data, key):
    """The batches the program's own sampler draws for `key`, a worker each."""
    draw = jax.jit(lambda k: bound._sample_ids(k, jnp.int32(0)))
    out = []
    for d in range(bound.n_workers):
        drawn = np.asarray(draw(jax.random.fold_in(key, d))) + d * bound.shard_n
        out += [(jnp.asarray(data.indices[r]), jnp.asarray(data.values[r]),
                 jnp.asarray(data.labels[r])) for r in drawn]
    return out


# -- the list format ----------------------------------------------------------------

def test_lists_and_dense_labels_are_one_another():
    y = _dense(200).labels
    lists, cut = to_lists(y)
    assert lists.dtype == np.int32 and cut == 0 and lists.shape[1] == (y > 0).sum(1).max()
    for row, ids in zip(lists[:64], y[:64]):
        kept = row[row >= 0]
        np.testing.assert_array_equal(kept, np.flatnonzero(ids > 0))  # ascending, pads last
        assert np.all(row[len(kept):] == LIST_PAD)
    np.testing.assert_array_equal(to_dense(lists, 200), y)
    narrow, cut = to_lists(y, width=2)  # a longer row keeps its lowest ids, and is counted
    assert cut == int(((y > 0).sum(1) > 2).sum()) > 0
    np.testing.assert_array_equal(narrow, lists[:, :2])


@pytest.mark.parametrize("width", [5, 8, 256])
def test_expanded_lists_are_the_dense_labels_with_their_masks(width):
    lists = jnp.asarray([[0, 3, LIST_PAD], [LIST_PAD] * 3, [LIST_NO_ROW] * 3, [4, LIST_PAD, LIST_PAD]],
                        jnp.int32)
    want = np.zeros((4, width), np.float32)
    want[:, :5] = [[1, -1, -1, 1, -1], [-1] * 5, [0] * 5, [-1, -1, -1, -1, 1]]
    np.testing.assert_array_equal(np.asarray(expand_labels(lists, 5, width)), want)


def test_a_dataset_refuses_lists_it_cannot_hold():
    idx, val = np.zeros((4, 2), np.int32), np.zeros((4, 2), np.float32)
    with pytest.raises(ValueError, match="label lists"):
        Dataset(idx, val, np.zeros((4, 3), np.float32), 10, n_labels=5)  # not integer ids
    with pytest.raises(ValueError, match="label lists"):
        Dataset(idx, val, np.zeros((4,), np.int32), 10, n_labels=5)  # not [N, Lw]
    with pytest.raises(ValueError, match="label lists"):
        Dataset(idx, val, np.zeros((4, 3), np.int32), 10, n_labels=1)  # one output: a flat label
    kept = Dataset(idx, val, np.zeros((4, 3), np.int32), 10, n_labels=5).slice(slice(1, 3))
    assert kept.n_labels == 5 and len(kept) == 2


def test_a_binding_refuses_lists_of_another_label_count():
    with pytest.raises(ValueError, match="list their labels among 200"):
        _bind(_listed(_dense(200)), 300)


# -- (i) one step and one evaluation against the plain reference ------------------------

@pytest.mark.parametrize("devices,workers", [(1, 4), (4, 1)])
@pytest.mark.parametrize("sparse", [False, True])
@pytest.mark.parametrize("reg", ["l2", "none"])
@pytest.mark.parametrize("n_outputs", [200, 300])
def test_one_step_equals_the_reference(n_outputs, reg, sparse, devices, workers, monkeypatch):
    monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 0 if sparse else 10**9)
    data = _listed(_dense(n_outputs))
    bound = _bind(data, n_outputs, reg=reg, devices=devices, workers=workers)
    assert (bound.kernel, bound.plan.update == "sparse", bound.plan.labels) == (
        "gather", sparse, "lists")
    w, key = _weights(n_outputs), jax.random.PRNGKey(7)
    want = np.asarray(reference_lists.sync_step(
        "squared_hinge", reg, w, _batches(bound, data, key), LAM, LR))
    got = np.asarray(bound.step(w, key))
    assert got.shape == (D, n_outputs)
    np.testing.assert_allclose(got - np.asarray(w), want - np.asarray(w), rtol=2e-5, atol=2e-7)


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("n_outputs", [200, 300])
def test_the_evaluation_equals_the_reference(n_outputs, devices):
    data = _listed(_dense(n_outputs)).slice(slice(0, 500))  # 500 rows: pad rows to mask
    bound = _bind(data, n_outputs, devices=devices, workers=1)
    assert bound.data.labels.shape == (512, data.labels.shape[1])  # stored as narrow as they come
    assert np.all(np.asarray(bound.data.labels)[500:] == LIST_NO_ROW)
    w = _weights(n_outputs)
    loss, acc = bound.evaluate(w)
    ref_loss, ref_acc = reference_lists.evaluate(
        "squared_hinge", w, data.indices, data.values, data.labels, LAM)
    assert loss == pytest.approx(ref_loss, rel=1e-5) and acc == pytest.approx(ref_acc, abs=1e-6)
    preds = bound.predict(w)
    assert preds.shape == (500, n_outputs) and set(np.unique(preds)) <= {-1.0, 1.0}
    y = to_dense(data.labels, n_outputs)
    assert np.mean(preds == y) == pytest.approx(acc, abs=1e-6)


# -- (ii) a fit on lists is the fit on the same labels as a dense array --------------------

def _fit(train, test, n_outputs, loss, epochs=2, seed=11):
    model = make_model(loss, LAM, D, regularizer="l2", n_outputs=n_outputs)
    trainer = SyncTrainer(model, make_mesh(1), BATCH, LR, seed=seed, virtual_workers=4,
                          metrics=metrics_mod.Metrics())
    return trainer.fit(train, test, max_epochs=epochs)


@pytest.mark.parametrize("loss", LOSSES)
def test_a_fit_on_lists_is_the_fit_on_dense_labels(loss):
    dense = _dense(200)
    lists = _listed(dense)
    cut = slice(0, 384), slice(384, None)
    a = _fit(dense.slice(cut[0]), dense.slice(cut[1]), 200, loss)
    b = _fit(lists.slice(cut[0]), lists.slice(cut[1]), 200, loss)
    np.testing.assert_array_equal(np.asarray(a.weights), np.asarray(b.weights))
    np.testing.assert_allclose(a.test_losses, b.test_losses, rtol=1e-6)
    np.testing.assert_allclose(a.test_accuracies, b.test_accuracies, rtol=1e-6)


# -- (iii) the squared hinge at one output, every family ----------------------------------

@pytest.mark.parametrize("kernel", ["mxu", "gather", "scalar"])
def test_the_squared_hinge_at_one_output_is_the_references_column(kernel):
    data = _dense(1)  # flat labels [N]
    model = make_model("squared_hinge", LAM, D, regularizer="l2")
    bound = SyncEngine(model, make_mesh(1), BATCH, LR, eval_chunk=64, kernel=kernel,
                       virtual_workers=4).bind(data)
    assert bound.kernel == kernel and bound.plan.labels == "gathered"
    w, key = _weights(1), jax.random.PRNGKey(9)
    as_lists = Dataset(data.indices, data.values,
                       np.where(data.labels > 0, 0, LIST_PAD).astype(np.int32)[:, None], D)
    want = reference_lists.sync_step("squared_hinge", "l2", w, _batches(bound, as_lists, key),
                                     LAM, LR)
    got = np.asarray(bound.step(w[:, 0], key))
    np.testing.assert_allclose(got - np.asarray(w[:, 0]), np.asarray(want - w)[:, 0],
                               rtol=2e-5, atol=2e-7)
    loss, acc = bound.evaluate(w[:, 0])
    ref_loss, ref_acc = reference_lists.evaluate(
        "squared_hinge", w, as_lists.indices, as_lists.values, as_lists.labels, LAM)
    assert loss == pytest.approx(ref_loss, rel=1e-5) and acc == pytest.approx(ref_acc, abs=1e-6)


def test_the_squared_hinges_derivative_is_continuous_at_the_kink():
    model = make_model("squared_hinge", LAM, D, regularizer="l2")
    m = jnp.asarray([1.0 - 1e-6, 1.0, 1.0 + 1e-6, -3.0, 0.0])
    coeff = np.asarray(model.grad_coeff(m, jnp.ones(5)))
    np.testing.assert_allclose(coeff, [-2e-6, 0.0, 0.0, -8.0, -2.0], atol=3e-7)
    np.testing.assert_allclose(np.asarray(model.losses_from_margins(m, jnp.ones(5))),
                               [0.0, 0.0, 0.0, 16.0, 1.0], atol=1e-6)
    assert np.all(np.asarray(model.grad_coeff(m, jnp.zeros(5))) == 0.0)  # a pad adds nothing


# -- (iv) the share test: ranges of labels side by side ARE the fit of all ---------------

def test_the_fits_of_label_ranges_are_the_columns_of_the_fit_of_all():
    """DiSMEC's Algorithm 1: a node fits its batch of labels over every row
    and nothing is exchanged.  Under the same draws the three fits of 100
    labels each, set side by side, equal the fit of all 300 column for
    column: nothing is computed alike on all shares, so nothing is counted
    twice."""
    dense = _dense(300)
    cut = slice(0, 384), slice(384, None)

    def fit(first, end):
        part = _listed(dense, first, end)
        return _fit(part.slice(cut[0]), part.slice(cut[1]), end - first, "squared_hinge")

    whole = fit(0, 300)
    shares = [fit(first, first + 100) for first in (0, 100, 200)]
    assert np.shape(whole.weights) == (D, 300)
    np.testing.assert_allclose(
        np.concatenate([np.asarray(s.weights) for s in shares], axis=1),
        np.asarray(whole.weights), rtol=1e-6, atol=1e-8)
    # the objective is the sum of the shares' (losses and lam ||W||^2 alike)
    np.testing.assert_allclose(np.sum([s.test_losses for s in shares], axis=0),
                               whole.test_losses, rtol=1e-5)


# -- (v) the walk of the sorted entries on rows of one, two and eight lane groups -----------

@pytest.mark.parametrize("lanes", [128, 256, 1024])
def test_the_row_write_on_wide_rows_is_the_float64_scatter_add(lanes, monkeypatch):
    """`scatter_rows_into` with the kernel `scatter_runs` (`_sum_runs_into`,
    Pallas' TPU interpret mode) on weight rows of 512 B (`[D', 128]`), 1 KB
    and 4 KB (tiles): every run of an id summed out of the coefficient
    table, every touched row read, added to and written once.  1,300
    entries are three blocks of the walk in two calls (236 pad entries on
    feature 0 in front); feature 1's run of 900 is longer than a block,
    opens inside the first, fills the second and ends in the third, across
    the calls' cut."""
    from jax.experimental.pallas import tpu as pltpu

    rng = np.random.default_rng(36)
    n_rows, n_entries, samples = 96, 1300, 24
    ids = np.minimum(np.exp(rng.uniform(0, np.log(n_rows + 1), n_entries)).astype(np.int64) - 1,
                     n_rows - 1).astype(np.int32)
    ids[:900] = 1
    assert 236 + np.sum(ids < 1) < gather.RUN_BLOCK and (
        2 * gather.RUN_BLOCK < 236 + np.sum(ids <= 1) < n_entries)
    monkeypatch.setattr(gather, "DMA_BLOCK", 2 * gather.RUN_BLOCK)  # two calls
    values = rng.normal(size=n_entries).astype(np.float32)
    src = rng.integers(0, samples, n_entries).astype(np.int32)
    coeff = (rng.normal(size=(samples, lanes)) * 1e-2).astype(np.float32)
    w2 = (rng.normal(size=(n_rows, lanes)) * 3.0).astype(np.float32)
    carried = gather.to_tiles if lanes > gather.LANES else (lambda w: w)

    def both(ids):
        want = w2.astype(np.float64)
        np.add.at(want, ids, values.astype(np.float64)[:, None] * coeff.astype(np.float64)[src])
        entries = tuple(jnp.asarray(a) for a in (ids, values, src, coeff))
        with pltpu.force_tpu_interpret_mode():
            got = np.asarray(jax.jit(lambda w: gather.scatter_rows_into(
                carried(w), *entries, "runs"))(jnp.asarray(w2)))
        xla = np.asarray(jax.jit(lambda w: gather.scatter_rows_into(w, *entries))(
            jnp.asarray(w2)))
        return got.reshape(w2.shape), xla, want

    got, xla, want = both(ids)
    # another order of addition inside a run than the MXU's, and nothing else
    np.testing.assert_allclose(got, xla, rtol=1e-6, atol=2e-6)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=2e-6)
    untouched = np.setdiff1d(np.arange(n_rows), ids)
    np.testing.assert_array_equal(got[untouched[untouched > 0]], w2[untouched[untouched > 0]])
    # a step of ONE distinct id: one run through every block, written once, at the end
    got, _, want = both(np.full(n_entries, 5, np.int32))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=2e-6)
    assert not kernels.merges_scatter(n_rows, lanes, n_entries) or lanes <= kernels.MERGE_MAX_LANES


@pytest.mark.parametrize("samples,width,lanes,piece", [
    (4096, 76, 128, 4096), (400, 72, 1024, 400), (4096, 72, 1024, 512), (64, 6, 256, 64)])
def test_the_margins_rule_cuts_a_wide_chunk_and_no_other(samples, width, lanes, piece):
    assert kernels.margin_rows(samples, width, lanes) == piece


def test_margins_in_pieces_are_the_margins_in_one(monkeypatch):
    data = _dense(200)
    w2 = gather.to_rows(_weights(200))
    from distributed_sgd_tpu.ops.sparse import SparseBatch

    batch = SparseBatch(jnp.asarray(data.indices[:64]), jnp.asarray(data.values[:64]))
    whole = np.asarray(gather.matvec_rows(batch, w2, "gather", 64))
    monkeypatch.setattr(kernels, "GATHERED_ROWS_MAX_BYTES", 16 * P * 256 * 4)
    assert kernels.margin_rows(64, P, 256) == 16
    np.testing.assert_allclose(np.asarray(gather.matvec_rows(batch, w2, "gather", 16)), whole,
                               rtol=1e-6, atol=1e-7)


# -- (v b) the margin kernel on tiles: each distinct tile of a piece fetched once (PR 40) ---

def _margin_case(case: str, rng):
    """(indices, values, piece) of one case of the margin kernel."""
    samples, width, piece = {"pieces": (64, P, 8), "step": (400, P, 100)}.get(case, (32, P, 16))
    ids = np.minimum(np.exp(rng.uniform(0, np.log(D + 1), (samples, width))).astype(np.int64) - 1,
                     D - 1).astype(np.int32)
    values = rng.normal(size=(samples, width)).astype(np.float32)
    if case == "all_distinct":  # the worst case the rule sizes the cache for
        ids = rng.permutation(D)[:samples * width].reshape(samples, width).astype(np.int32)
    elif case == "one_id":
        ids[:] = 7
    elif case == "pads":  # a row's unused entries: 0.0 on feature 0
        ids[:, 4:], values[:, 4:] = 0, 0.0
    elif case == "no_row":  # LIST_NO_ROW padding rows: every entry a pad
        ids[::3], values[::3] = 0, 0.0
    return ids, values, piece


@pytest.mark.parametrize("lanes", [256, 1024])
@pytest.mark.parametrize("case", ["all_distinct", "one_id", "pads", "no_row", "pieces", "step",
                                  "binding"])
def test_the_margin_kernel_is_the_float64_product(case, lanes, monkeypatch):
    """`_margin_tiles` (Pallas' TPU interpret mode) on tiles of 1 KB and
    4 KB: a piece's distinct tiles fetched once into a cache slot, a
    sample's tiles summed in a register, against float64 `x . W` and
    XLA's gather of a tile an entry.  Pads add 0 x tile 0, a padding row's
    margins are 0.  The kernel on the pieces' plan (`plan_pieces`: it
    walks no ids) is the walking kernel bit for bit; and a binding whose
    evaluation's margins are planned evaluates and predicts what the same
    binding walking them does, bit for bit (`binding`)."""
    from jax.experimental.pallas import tpu as pltpu

    from distributed_sgd_tpu.ops.sparse import SparseBatch

    if case == "binding":
        _a_planned_binding_is_the_walking_one(lanes, monkeypatch)
        return
    rng = np.random.default_rng(40)
    ids, values, piece = _margin_case(case, rng)
    w2 = (rng.normal(size=(D, lanes)) * 0.5).astype(np.float32)
    batch = SparseBatch(jnp.asarray(ids), jnp.asarray(values))
    want = np.einsum("bp,bpl->bl", values.astype(np.float64), w2.astype(np.float64)[ids])
    xla = np.asarray(gather.matvec_rows(batch, gather.to_tiles(jnp.asarray(w2)), "gather",
                                        ids.shape[0]))
    # the sort of one word an entry, then of two (ids that leave no room
    # for the position) with turns that leave a remainder
    walked = []
    for n_rows, constants in ((D, {}), (2 ** 32, {"unroll": 4})):
        with pltpu.force_tpu_interpret_mode():
            got = np.asarray(jax.jit(lambda w: gather._margin_tiles(
                w, *gather._sorted_pieces(batch, piece, n_rows), piece, ids.shape[1],
                **constants))(
                    gather.to_tiles(jnp.asarray(w2)))).reshape(len(ids), lanes)
        # another order of addition inside a sample, and nothing else
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-6)
        np.testing.assert_allclose(got, xla, rtol=1e-5, atol=2e-6)
        walked.append(got)
    with pltpu.force_tpu_interpret_mode():  # the walking kernel's constants
        planned = np.asarray(jax.jit(lambda w, plan: gather.matvec_rows(
            batch, w, "planned", piece, plan=plan))(
                gather.to_tiles(jnp.asarray(w2)), gather.plan_pieces(batch.indices, piece, D)))
    np.testing.assert_array_equal(planned, walked[0])
    if case == "no_row":
        assert not np.any(got[::3])


def _a_planned_binding_is_the_walking_one(lanes: int, monkeypatch):
    """A binding on tiles of `lanes` lanes (on a steered TPU, the kernels in
    Pallas' interpret mode) whose evaluation's chunks of 64 samples take
    the margin kernel in pieces of 16 on the margin plan it made at bind,
    against the same binding with the evaluation's fetch 'distinct' (the
    walking kernel): `evaluate` and `predict` equal bit for bit, and the
    evaluation the float64 reference's."""
    import dataclasses

    from jax.experimental.pallas import tpu as pltpu

    from distributed_sgd_tpu.ops import mxu
    from distributed_sgd_tpu.parallel.sync import BoundSync

    outputs = {256: 200, 1024: 1000}[lanes]
    monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 0)
    monkeypatch.setattr(kernels, "MERGE_MAX_ROWS_PER_ENTRY", 0)
    monkeypatch.setattr(kernels, "MARGIN_TILES_MIN_LANES", lanes)
    # a piece's worst case fits at 16 samples of P entries and no more
    tile = -(-lanes // 1024) * 4096
    monkeypatch.setattr(kernels, "MARGIN_VMEM_BYTES", tile * (16 * P + 2 * 16))
    monkeypatch.setattr(mxu, "blocked_pays_off", lambda device=None: True)
    data = _listed(_dense(outputs)).slice(slice(0, 128))
    model = make_model("squared_hinge", LAM, D, regularizer="l2", n_outputs=outputs)
    planned = SyncEngine(model, make_mesh(1), BATCH, LR, eval_chunk=64, kernel="gather",
                         virtual_workers=4).bind(data)
    assert planned.plan.eval_fetch == kernels.Fetch("planned", 16)
    assert planned.margin_plan.heads.shape == (len(data) // 64, 4)
    walked = BoundSync(model, planned.mesh, planned.data, BATCH, LR, eval_chunk=64,
                       virtual_workers=4, plan=dataclasses.replace(
                           planned.plan, eval_fetch=kernels.Fetch("distinct", 16)))
    assert walked.margin_plan is None
    w = _weights(outputs)
    with pltpu.force_tpu_interpret_mode():
        got = [(bound.evaluate(w), bound.predict(w)) for bound in (planned, walked)]
    assert got[0][0] == got[1][0]
    np.testing.assert_array_equal(got[0][1], got[1][1])
    loss, acc = reference_lists.evaluate("squared_hinge", w, data.indices, data.values,
                                         data.labels, LAM)
    np.testing.assert_allclose(got[0][0], (loss, acc), rtol=1e-5)


def _plan_case(case: str, rng):
    """(indices int32[samples, P], piece) of one case of the margin plan."""
    samples, piece = (4096, 256) if case == "chunk" else (64, 16)
    ids = np.minimum(np.exp(rng.uniform(0, np.log(D + 1), (samples, P))).astype(np.int64) - 1,
                     D - 1).astype(np.int32)
    if case == "all_distinct":
        ids = rng.permutation(D)[:samples * P].reshape(samples, P).astype(np.int32)
    elif case == "one_id":
        ids[:] = 7
    elif case == "past_the_rows":  # clamped to the last row, as XLA's gather clamps
        ids[::5, 3] = D + 17
        ids[1, :] = 2 ** 31 - 1
    elif case == "no_row":  # padding rows: every entry a pad of feature 0
        ids[::3] = 0
    return ids, piece


@pytest.mark.parametrize("case", ["law", "all_distinct", "one_id", "past_the_rows", "no_row",
                                  "chunk"])
def test_the_margin_plan_is_each_pieces_distinct_tiles_and_every_entrys_slot(case):
    """`gather.plan_pieces` (XLA: three batched sorts, no walk) against
    NumPy: per piece the distinct ids, clamped into the weights' rows,
    ascending in `to`, their count in `heads`, and every entry's slot, in
    sample order, naming its own id.  Pieces of 16 samples of a batch of
    64, and the evaluation's chunk of 4,096 in pieces of 256."""
    rng = np.random.default_rng(45)
    ids, piece = _plan_case(case, rng)
    rows = D + 8  # the weights' rows: D rounded up to whole sublanes, and more
    plan = jax.jit(lambda i: gather.plan_pieces(i, piece, rows))(jnp.asarray(ids))
    to, slots, heads = (np.asarray(a) for a in plan)
    per = piece * P
    assert to.shape == slots.shape == (len(ids) // piece, per + -per % gather.FACTOR_ALIGN)
    clamped = np.minimum(ids, rows - 1).reshape(-1, per)
    for j, entries in enumerate(clamped):
        distinct = np.unique(entries)
        assert heads[j] == distinct.size
        np.testing.assert_array_equal(to[j, :heads[j]], distinct)
        np.testing.assert_array_equal(to[j, :heads[j]][slots[j, :per]], entries)
    if case == "all_distinct":
        assert np.all(heads == per)
    if case == "one_id":
        assert np.all(heads == 1) and not np.any(slots[:, :per])
    if case == "no_row":  # a piece of 16 rows holds 5 or 6 padding rows: feature 0 is a tile
        assert np.all(to[:, 0] == 0)


@pytest.mark.parametrize("samples,width,lanes,piece", [
    (4096, 72, 1024, 256),  # the evaluation's chunk: 18,432 tiles of 4 KB in VMEM
    (400, 72, 1024, 200),   # a step of 4 x 100 samples
    (4096, 76, 128, 0),     # rcv1-topics-hinge's 512 B rows: XLA's gather
    (64, 6, 256, 0),        # two lane groups: no cell, nothing measured
    (399, 72, 1024, 0),     # odd, and never fits
    (64, 6, 2048, 64)])
def test_the_margin_kernel_rule_cuts_pieces_from_shapes_alone(samples, width, lanes, piece):
    assert kernels.margin_tiles(samples, width, lanes) == piece


# -- (v c) the wide-row kernels' unchecked DMAs, on runs of neighbouring ids --------------

RUN_ROWS, RUN_LANES = 480, 256  # tiles of two lane groups


def _run_case_ids(case: str, n: int, rng) -> np.ndarray:
    """`n` entry ids over RUN_ROWS rows for one case.  Where a case plants
    a run of neighbouring ids, every other id is a multiple of 3 away from
    it, so no other run touches it."""
    spread = 3 * rng.integers(0, RUN_ROWS // 3, n)
    if case in ("law", "past_dma_block"):
        return np.minimum(np.exp(rng.uniform(0, np.log(RUN_ROWS + 1), n)).astype(np.int64) - 1,
                          RUN_ROWS - 1)
    if case == "spread":  # no two distinct ids neighbours
        return spread
    if case == "one_id":
        return np.full(n, 7)
    if case in ("across_block", "block_end"):
        # the scatter pads 330 entries with 54 of feature 0 in front and
        # sorts them: 60 ids under 150 put the run at sorted entries 114..,
        # across the first block of 128 or ending at its last entry
        assert n == 330 and gather.RUN_BLOCK == 128
        length = 30 if case == "across_block" else 14
        below = 3 * rng.integers(1, 50, 60)
        above = 3 * rng.integers(61 + length // 3, RUN_ROWS // 3, n - 60 - length)
        return np.concatenate([below, 150 + np.arange(length), above])
    length = int(case[3:])  # "run64", "run65", "run200": 150 .. 150 + length - 1 once each
    rest = spread[length:]
    rest[(rest >= 147) & (rest <= 151 + length)] = 3
    return rng.permutation(np.concatenate([150 + np.arange(length), rest]))


def _apart(ids: np.ndarray, w: np.ndarray):
    """The same call with every id doubled: row 2 i of the wider weights is
    row i, so no two distinct ids are neighbours, and the order of every
    sort and of every addition is unchanged (the ids keep their order)."""
    wide = np.zeros((2 * w.shape[0],) + w.shape[1:], w.dtype)
    wide[0::2] = w
    return 2 * ids, wide


@pytest.mark.parametrize("kernel,case", [
    ("scatter", "law"), ("scatter", "spread"), ("scatter", "across_block"),
    ("scatter", "block_end"), ("scatter", "run64"), ("scatter", "run65"),
    ("scatter", "run200"), ("scatter", "one_id"), ("scatter", "past_dma_block"),
    ("margins", "law"), ("margins", "spread"), ("margins", "piece_end"),
    ("margins", "run64"), ("margins", "run65"), ("margins", "run200"),
    ("margins", "one_id")])
def test_the_wide_row_kernels_see_only_the_order_of_the_ids(kernel, case, monkeypatch):
    """`scatter_runs` (`_sum_runs_into`, blocks of 128 entries here) and
    `margin_tiles` (Pallas' TPU interpret mode) on runs of neighbouring
    tiles: bit for bit what the same call gives where no two ids are
    neighbours (`_apart`), and the float64 sums to the order of addition.
    The ids: under the generator's law; no neighbours; a run across a block
    of the walk and one ending at its last entry; runs of 64, 65 and 200
    tiles; one id in every entry; the scatter's entries in two calls
    (`DMA_BLOCK`); a run that ends at a margin piece's last slot."""
    from jax.experimental.pallas import tpu as pltpu

    from distributed_sgd_tpu.ops.sparse import SparseBatch

    rng = np.random.default_rng(41)
    w = (rng.normal(size=(RUN_ROWS, RUN_LANES)) * 0.5).astype(np.float32)
    if kernel == "scatter":
        samples, n = 24, 330
        monkeypatch.setattr(gather, "RUN_BLOCK", 128)
        if case == "past_dma_block":
            monkeypatch.setattr(gather, "DMA_BLOCK", 2 * 128)  # two calls
        ids = _run_case_ids(case, n, rng).astype(np.int32)
        values = rng.normal(size=n).astype(np.float32)
        src = rng.integers(0, samples, n).astype(np.int32)
        coeff = (rng.normal(size=(samples, RUN_LANES)) * 1e-2).astype(np.float32)

        def call(ids, w):
            with pltpu.force_tpu_interpret_mode():
                return np.asarray(jax.jit(lambda w: gather.scatter_rows_into(
                    w, jnp.asarray(ids), jnp.asarray(values), jnp.asarray(src),
                    jnp.asarray(coeff), "runs"))(gather.to_tiles(jnp.asarray(w))))

        want = w.astype(np.float64)
        np.add.at(want, ids, values.astype(np.float64)[:, None] * coeff.astype(np.float64)[src])
        got, apart = call(ids, w), call(*_apart(ids, w))
        np.testing.assert_array_equal(got, apart[0::2])
        assert not np.any(apart[1::2])
        np.testing.assert_allclose(got.reshape(w.shape), want, rtol=1e-6, atol=2e-6)
        return
    samples, width, piece = 64, 8, 32
    ids = 3 * rng.integers(0, RUN_ROWS // 3, (samples, width))
    if case == "piece_end":  # the first piece's largest ids: a run to its last slot
        ids[:piece] = 3 * rng.integers(0, 100, (piece, width))
        ids[:4] = 400 + np.arange(32).reshape(4, width)
    elif case.startswith("run"):  # the run lies in the first piece
        ids[:piece] = _run_case_ids(case, piece * width, rng).reshape(piece, width)
    elif case != "spread":
        ids = _run_case_ids(case, samples * width, rng).reshape(samples, width)
    ids = ids.astype(np.int32)
    values = rng.normal(size=(samples, width)).astype(np.float32)

    def margins(ids, w):
        batch = SparseBatch(jnp.asarray(ids), jnp.asarray(values))
        with pltpu.force_tpu_interpret_mode():
            return np.asarray(jax.jit(lambda w: gather._margin_tiles(
                w, *gather._sorted_pieces(batch, piece, w.shape[0]), piece, width))(
                    gather.to_tiles(jnp.asarray(w))))

    got = margins(ids, w)
    np.testing.assert_array_equal(got, margins(*_apart(ids, w)))
    want = np.einsum("bp,bpl->bl", values.astype(np.float64), w.astype(np.float64)[ids])
    np.testing.assert_allclose(got.reshape(samples, RUN_LANES), want, rtol=1e-5, atol=2e-6)


@pytest.mark.parametrize("kernel", ["scatter", "margins"])
def test_an_id_past_the_weights_is_clamped_by_the_margins_and_dropped_by_the_scatter(kernel):
    """The wide-row kernels start their DMAs unchecked
    (`disable_bounds_checks`), so an id past the weights is put in range
    before the call: the margins read the last row for it, as XLA's gather
    clamps it, and the scatter adds nothing for it, as XLA's scatter drops
    it: the kernels (Pallas' TPU interpret mode) against XLA's paths."""
    from jax.experimental.pallas import tpu as pltpu

    from distributed_sgd_tpu.ops.sparse import SparseBatch

    rng = np.random.default_rng(42)
    w = (rng.normal(size=(RUN_ROWS, RUN_LANES)) * 0.5).astype(np.float32)
    tiles = gather.to_tiles(jnp.asarray(w))
    if kernel == "scatter":
        samples, n = 24, 300
        ids = _run_case_ids("law", n, rng).astype(np.int32)
        ids[::7] = RUN_ROWS + rng.integers(0, 1000, len(ids[::7]))
        values = rng.normal(size=n).astype(np.float32)
        src = rng.integers(0, samples, n).astype(np.int32)
        coeff = (rng.normal(size=(samples, RUN_LANES)) * 1e-2).astype(np.float32)
        entries = tuple(jnp.asarray(a) for a in (ids, values, src, coeff))
        with pltpu.force_tpu_interpret_mode():
            got = np.asarray(jax.jit(lambda w: gather.scatter_rows_into(w, *entries, "runs"))(
                tiles)).reshape(w.shape)
        xla = np.asarray(jax.jit(lambda w: gather.scatter_rows_into(w, *entries))(jnp.asarray(w)))
        kept = ids < RUN_ROWS
        want = w.astype(np.float64)
        np.add.at(want, ids[kept], values[kept].astype(np.float64)[:, None]
                  * coeff.astype(np.float64)[src[kept]])
        np.testing.assert_allclose(got, xla, rtol=1e-6, atol=2e-6)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=2e-6)
        return
    samples, width, piece = 32, 8, 16
    ids = _run_case_ids("law", samples * width, rng).reshape(samples, width).astype(np.int32)
    ids[::5, 3] = RUN_ROWS + 17
    values = rng.normal(size=(samples, width)).astype(np.float32)
    batch = SparseBatch(jnp.asarray(ids), jnp.asarray(values))
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(jax.jit(lambda w: gather._margin_tiles(
            w, *gather._sorted_pieces(batch, piece, RUN_ROWS), piece, width))(tiles))
    xla = np.asarray(gather.matvec_rows(batch, tiles, "gather", samples))
    want = np.einsum("bp,bpl->bl", values.astype(np.float64),
                     w.astype(np.float64)[np.minimum(ids, RUN_ROWS - 1)])
    np.testing.assert_allclose(got.reshape(samples, RUN_LANES), xla, rtol=1e-5, atol=2e-6)
    np.testing.assert_allclose(got.reshape(samples, RUN_LANES), want, rtol=1e-5, atol=2e-6)


@pytest.mark.parametrize("outputs,on_tpu,fetch", [
    (1000, True, "distinct"),  # tiles of eight lane groups on a TPU
    (1000, False, "gather"),   # off the TPU: XLA's gather, the tests' reference
    (103, True, "gather"),     # [D', 128] rows, rcv1-topics-hinge's shape
    (1, True, "gather"),       # flat w, kdd2012-logistic's shape
])
def test_only_tiles_on_a_tpu_fetch_distinct_tiles(outputs, on_tpu, fetch, monkeypatch, caplog):
    """Which bindings take the margin kernel (`fetch`: a step's): counted
    once a binding under `bind.margins.tiles`, said as `margin_fetch=` on
    the `train split:` record.  Its evaluation reads the margin plan the
    binding made once (`planned`, counted under `bind.margins.planned`),
    and no other binding makes one."""
    from distributed_sgd_tpu.ops import mxu

    monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 0)
    monkeypatch.setattr(kernels, "MERGE_MAX_ROWS_PER_ENTRY", 0)
    monkeypatch.setattr(mxu, "blocked_pays_off", lambda device=None: on_tpu)
    data = _dense(outputs) if outputs > 1 else rcv1_like(N, n_features=D, nnz=P, seed=3)
    counters = [metrics_mod.global_metrics().counter(f"bind.margins.{name}")
                for name in ("tiles", "planned")]
    before = [counter.value for counter in counters]
    model = make_model("squared_hinge" if outputs > 1 else "logistic", LAM, D,
                       regularizer="l2", n_outputs=outputs)
    trainer = SyncTrainer(model, make_mesh(1), BATCH, LR, virtual_workers=4, kernel="gather",
                          metrics=metrics_mod.Metrics())
    bound = trainer.engine.bind(data)
    distinct = fetch == "distinct"
    assert bound.plan.step_fetch.how == fetch
    assert bound.plan.eval_fetch.how == ("planned" if distinct else fetch)
    assert [counter.value for counter in counters] == [b + distinct for b in before]
    assert (bound.margin_plan is not None) == distinct
    if on_tpu:
        return  # a fit would run the TPU's kernels
    with caplog.at_level(logging.INFO, logger="dsgd.trainer"):
        trainer.fit(data.slice(slice(0, 384)), data.slice(slice(384, None)), max_epochs=1)
    record = next(r.getMessage() for r in caplog.records
                  if r.getMessage().startswith("train split:"))
    assert f"margin_fetch={fetch}" in record


def test_on_a_tpu_the_margins_of_tiles_are_the_gathers(monkeypatch):
    """An epoch and an evaluation of a binding on 1,024-lane tiles with the
    margin kernel (Pallas' interpret mode) against the same binding off
    the TPU: the step's margins and the evaluation's sums agree to the
    order of addition inside a sample."""
    from jax.experimental.pallas import tpu as pltpu

    from distributed_sgd_tpu.ops import mxu

    monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 0)
    monkeypatch.setattr(kernels, "MERGE_MAX_ROWS_PER_ENTRY", 0)
    data = _listed(_dense(1000))
    got = []
    for on_tpu in (False, True):
        monkeypatch.setattr(mxu, "blocked_pays_off", lambda device=None, said=on_tpu: said)
        bound = SyncEngine(make_model("squared_hinge", LAM, D, regularizer="l2", n_outputs=1000),
                           make_mesh(1), BATCH, LR, eval_chunk=64, kernel="gather",
                           virtual_workers=4).bind(data, steps_per_epoch=2)
        assert (bound.plan.eval_fetch.how == "planned") == on_tpu
        w, key = _weights(1000), jax.random.PRNGKey(4)
        with pltpu.force_tpu_interpret_mode():
            got.append((np.asarray(bound.epoch(w, key)), np.asarray(bound.evaluate(w))))
    np.testing.assert_allclose(got[1][0], got[0][0], rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(got[1][1], got[0][1], rtol=1e-5)


# -- (vi) the text format ------------------------------------------------------------------

FILE = """5 10 6
0,3 1:0.5 4:0.25
 2:1.0
5 7:0.5 9:0.125
1,2,4 0:1.0
3 5:2.0 6:0.5 8:0.25
"""


def test_the_reader_keeps_a_label_range_as_lists(tmp_path):
    path = tmp_path / "train.txt"
    path.write_text(FILE)
    every = read_multilabel(str(path))
    assert (every.n_features, every.n_labels, len(every)) == (10, 6, 5)
    np.testing.assert_array_equal(every.labels, [
        [0, 3, -1], [-1, -1, -1], [5, -1, -1], [1, 2, 4], [3, -1, -1]])
    np.testing.assert_array_equal(every.indices[0], [1, 4, 0])
    np.testing.assert_allclose(every.values[4], [2.0, 0.5, 0.25])
    # the batch [2, 5): ids renumbered from 2; rows 0, 3, 4 keep some, rows 1, 2 none
    part = read_multilabel(str(path), label_range=(2, 5), list_width=2)
    assert part.n_labels == 3 and part.labels.dtype == np.int32
    np.testing.assert_array_equal(part.labels, [[1, -1], [-1, -1], [-1, -1], [0, 2], [1, -1]])
    np.testing.assert_array_equal(part.indices, every.indices)
    with pytest.raises(ValueError, match="label range"):
        read_multilabel(str(path), label_range=(4, 9))
    (tmp_path / "short.txt").write_text(FILE.replace("5 10 6", "6 10 6"))
    with pytest.raises(ValueError, match="the header says 6 rows"):
        read_multilabel(str(tmp_path / "short.txt"))


def test_main_builds_the_squared_hinge_with_its_outputs_from_lists(monkeypatch, tmp_path):
    from distributed_sgd_tpu import main as program
    from distributed_sgd_tpu.config import Config

    monkeypatch.setenv("DSGD_SYNTHETIC", "400")
    train, test, model = program.build(Config(labels="lists", model="squared_hinge"))
    assert train.n_labels == test.n_labels == program.SYNTHETIC_TOPICS
    assert train.labels.dtype == np.int32 and train.labels.shape[0] == 320
    assert type(model).__name__ == "SquaredHinge"
    assert (model.n_outputs, model.regularizer) == (program.SYNTHETIC_TOPICS, "l2")
    # the same rows and labels as labels='topics' hands out dense
    dense, _test, _model = program.build(Config(labels="topics"))
    np.testing.assert_array_equal(to_dense(train.labels, train.n_labels), dense.labels)
    # a file in the repository's format, every label an output
    monkeypatch.delenv("DSGD_SYNTHETIC")
    (tmp_path / "train.txt").write_text(FILE)
    train, _test, model = program.build(
        Config(labels="lists", model="squared_hinge", data_path=str(tmp_path)))
    assert (train.n_features, model.n_outputs, model.weight_shape) == (10, 6, (10, 6))
    with pytest.raises(ValueError, match="model"):
        Config(model="squared")


# -- (vii) the scope, the counter, the record --------------------------------------------

def _scopes(lowered):
    return set(re.findall(r"dsgd\.[a-z_]+", lowered.compile().as_text()))


@pytest.mark.parametrize("sparse", [False, True])
def test_the_compiled_programs_carry_the_labels_scope(sparse, monkeypatch):
    monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 0 if sparse else 10**9)
    w, key = _weights(200), jax.random.PRNGKey(0)
    for data, said in ((_listed(_dense(200)), True), (_dense(200), False)):
        bound = _bind(data, 200)
        d = bound.data
        epoch = bound._epoch.lower(w, bound._opt_state, d.indices, d.values, d.labels, key)
        evaluation = bound._eval.lower(w, d.indices, d.values, d.labels)
        assert ("dsgd.labels" in _scopes(epoch)) == said
        assert ("dsgd.labels" in _scopes(evaluation)) == said
        if said:  # nested in the evaluation's own scope
            assert re.search(r"dsgd\.eval/[^\"]*dsgd\.labels", evaluation.compile().as_text())


def test_a_binding_on_lists_is_counted_and_logged(caplog):
    counter = metrics_mod.global_metrics().counter("bind.labels.lists")
    gathered = metrics_mod.global_metrics().counter("bind.labels.gathered")
    before, before_gathered = counter.value, gathered.value
    _bind(_dense(200), 200)
    assert (counter.value, gathered.value) == (before, before_gathered + 1)
    data = _listed(_dense(200))
    model = make_model("squared_hinge", LAM, D, regularizer="l2", n_outputs=200)
    trainer = SyncTrainer(model, make_mesh(1), BATCH, LR, virtual_workers=4,
                          metrics=metrics_mod.Metrics())
    with caplog.at_level(logging.INFO, logger="dsgd.trainer"):
        trainer.fit(data.slice(slice(0, 384)), data.slice(slice(384, None)), max_epochs=1)
    assert (counter.value, gathered.value) == (before + 2, before_gathered + 1)
    record = next(r.getMessage() for r in caplog.records
                  if r.getMessage().startswith("train split:"))
    for said in ("kernel=gather", "outputs=200", "labels=lists", "scatter=words", "eval_rows=384"):
        assert said in record


def test_on_a_tpu_a_wide_binding_writes_rows_and_does_not_merge(monkeypatch, caplog):
    """The cell's own shape asked of the rules: 203,882 features against
    28,800 entries a step and 1,024 lanes are the other side of
    `merges_scatter` twice over."""
    assert not kernels.merges_scatter(203_882, 1_000, 28_800)
    assert kernels.merges_scatter(203_882, 1_000, 60_000) is False  # the lanes alone
    assert kernels.sparse_update("gather", "l2", "sgd", 1e-7, 203_882, 1_000)
    assert kernels.choose_kernel(203_882, 72, "tpu", "mxu", 1_000) == "gather"
    from jax.experimental.pallas import tpu as pltpu

    from distributed_sgd_tpu.ops import mxu

    monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 0)
    monkeypatch.setattr(kernels, "MERGE_MAX_ROWS_PER_ENTRY", 0)
    monkeypatch.setattr(mxu, "blocked_pays_off", lambda device=None: True)
    data = _listed(_dense(200))
    want = None
    for on_tpu in (False, True):
        monkeypatch.setattr(mxu, "blocked_pays_off", lambda device=None, said=on_tpu: said)
        bound = SyncEngine(make_model("squared_hinge", LAM, D, regularizer="l2", n_outputs=200),
                           make_mesh(1), BATCH, LR, eval_chunk=64, kernel="gather",
                           virtual_workers=4).bind(data, steps_per_epoch=3)
        assert bound.plan.scatter == ("runs" if on_tpu else "words")
        w, key = _weights(200), jax.random.PRNGKey(3)
        with pltpu.force_tpu_interpret_mode():
            got = np.asarray(bound.epoch(w, key))
        if want is None:
            want = got
    # the kernel's sums are XLA's in another order of addition inside a run
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
