"""Where `SyncEngine.bind` places the resident rows (parallel/mesh.py
`lane_width` / `put_rows`, PERF.md section 6, PR 25).

On a TPU a [rows, width] array whose width is not whole 128-lane tiles is
stored rows-minor while the step gathers whole rows, so wide-enough rows are
stored zero-padded to whole lanes (row-major by default) and every reader
takes the true width back off.  The chip is out of tier-1's reach, so these
tests hold what the CPU can: (a) the rule as a pure function; (b) with the
rule made to pad on the CPU too, the padded arrays hold the rows and zeros,
and every program of a binding gives bit-identical results to the unpadded
placement; (c) the counters and the span that say it engaged.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sgd_tpu import trace as trace_mod
from distributed_sgd_tpu.data.rcv1 import Dataset
from distributed_sgd_tpu.data.synthetic import rcv1_like
from distributed_sgd_tpu.models.linear import make_model
from distributed_sgd_tpu.ops import mxu as mxu_mod
from distributed_sgd_tpu.parallel import mesh as mesh_mod
from distributed_sgd_tpu.parallel.local_sgd import LocalSGDEngine
from distributed_sgd_tpu.parallel.mesh import lane_width, make_mesh
from distributed_sgd_tpu.parallel.sync import SyncEngine
from distributed_sgd_tpu.utils import metrics as metrics_mod


# -- (a) the rule --------------------------------------------------------------

@pytest.mark.parametrize("platform", ["tpu", "cpu"])
@pytest.mark.parametrize("shape,on_tpu", [
    ((4096, 76), None),      # rcv1: 128 / 80 = 1.6x the bytes, left alone
    ((4096, 2000), 2048),    # epsilon: 2048 / 2000 = 1.024x
    ((4096, 128), None),     # whole lanes: stored row-major as it comes
    ((4096, 0), None),       # dense data's index array: no rows to gather
    ((4096,), None),         # labels
])
def test_rule_reads_only_shape_and_platform(shape, on_tpu, platform):
    assert lane_width(shape, platform) == (on_tpu if platform == "tpu" else None)


def test_rule_turns_over_at_one_eighth_more_bytes():
    # 120 wide: 128 / 120 = 1.067 pays; 112 wide: 128 / 112 = 1.143 does not
    assert lane_width((8, 120), "tpu") == 128
    assert lane_width((8, 112), "tpu") is None
    assert lane_width((8, 1930), "tpu") == 2048
    assert mesh_mod.ROW_MAJOR_MAX_PADDING == 1.125


# -- (b) readers never see the padding -----------------------------------------

def _pad_everywhere(monkeypatch):
    """The rule as on a TPU with no byte limit: every 2-D array with columns
    is stored padded to whole lanes, on the CPU too."""
    monkeypatch.setattr(mesh_mod, "ROW_MAJOR_MAX_PADDING", float("inf"))
    rule = mesh_mod.lane_width
    monkeypatch.setattr(mesh_mod, "lane_width", lambda shape, platform: rule(shape, "tpu"))


def _data(kind: str) -> Dataset:
    if kind == "sparse":
        return rcv1_like(256, n_features=64, nnz=6, seed=3)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(256, 24)).astype(np.float32)
    return Dataset.dense(x, np.where(rng.random(256) < 0.5, 1, -1).astype(np.int32))


def _bind(kind: str, devices: int):
    data = _data(kind)
    model = make_model("hinge", 1e-3, data.n_features)
    engine = SyncEngine(model, make_mesh(devices), batch_size=8,
                        learning_rate=0.1, eval_chunk=32, virtual_workers=2)
    return engine.bind(data)


def _everything(bound):
    w0 = jnp.asarray(np.random.default_rng(5).normal(
        size=bound.model.n_features) * 0.1, jnp.float32)
    key = jax.random.PRNGKey(11)
    w_epoch = bound.epoch(w0, key)
    return {"epoch": np.asarray(w_epoch), "step": np.asarray(bound.step(w0, key)),
            "evaluate": np.asarray(bound.evaluate(w_epoch)),
            "predict": bound.predict(w_epoch)}


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("kind", ["dense", "sparse"])
def test_programs_read_padded_rows_bit_for_bit(monkeypatch, kind, devices):
    plain = _bind(kind, devices)
    want = _everything(plain)

    _pad_everywhere(monkeypatch)
    bound = _bind(kind, devices)
    d, width = bound.data, plain.data.values.shape[1]
    assert (d.width, d.values.shape, d.labels.shape) == (width, (256, 128), (256,))
    assert d.indices.shape == ((256, 0) if kind == "dense" else (256, 128))
    for name in ("indices", "values"):  # the rows, then zeros
        stored, rows = np.asarray(getattr(d, name)), np.asarray(getattr(plain.data, name))
        np.testing.assert_array_equal(stored[:, :rows.shape[1]], rows)
        assert not stored[:, rows.shape[1]:].any()
    assert d.values.sharding == plain.data.values.sharding
    assert bound.placement()[0][2] == (0, 1)

    got = _everything(bound)
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)

    # the entry computation's parameters are the bound arrays as stored:
    # nothing is left for the compiler to bridge with a copy
    w0 = jnp.zeros((bound.model.n_features,), jnp.float32)
    args, _kwargs = bound._epoch.lower(
        w0, bound._opt_state, d.indices, d.values, d.labels,
        jax.random.PRNGKey(0)).compile().input_formats
    compiled_for = dict(zip(("indices", "values", "labels"), args[-4:-1]))
    if kind == "dense":
        del compiled_for["indices"]  # zero-width: the program never reads it
    assert compiled_for == {name: getattr(d, name).format for name in compiled_for}


def test_padding_program_walks_uneven_shards(monkeypatch):
    # 3 devices x 28 rows: gcd(28, 4096) = 4, seven pieces a shard
    _pad_everywhere(monkeypatch)
    x = np.arange(84 * 5, dtype=np.float32).reshape(84, 5)
    sharding = jax.sharding.NamedSharding(make_mesh(3), jax.sharding.PartitionSpec("workers"))
    stored = mesh_mod.put_rows(x, sharding)
    assert stored.shape == (84, 128) and stored.sharding == sharding
    np.testing.assert_array_equal(np.asarray(stored), np.pad(x, ((0, 0), (0, 123))))


def test_local_sgd_reads_padded_rows_bit_for_bit(monkeypatch):
    def fit():
        data = _data("dense")
        engine = LocalSGDEngine(make_model("hinge", 1e-3, data.n_features), make_mesh(2),
                                batch_size=8, learning_rate=0.1, sync_period=4,
                                check_every=16, seed=1)
        return np.asarray(engine.fit(data, data, max_epochs=1).weights)

    want = fit()
    _pad_everywhere(monkeypatch)
    np.testing.assert_array_equal(fit(), want)


# -- (c) the counters and the span ---------------------------------------------

def _counts():
    return {name: metrics_mod.counter(f"bind.rows.{name}").value
            for name in ("row_major", "default")}


def test_one_counter_and_one_span_per_placed_array(monkeypatch, tmp_path):
    tracer = trace_mod.configure(enabled=True, dir=str(tmp_path), sample=1.0,
                                 service="t")
    try:
        spans_before = metrics_mod.histogram("span.sync.bind.place").count
        before = _counts()
        bound = _bind("dense", 1)  # the CPU's rule: every array as it comes
        after = _counts()
        assert (after["default"] - before["default"],
                after["row_major"] - before["row_major"]) == (3, 0)

        _pad_everywhere(monkeypatch)
        _bind("dense", 1)  # values padded; zero-width indices and labels not
        last = _counts()
        assert (last["default"] - after["default"],
                last["row_major"] - after["row_major"]) == (2, 1)
        assert metrics_mod.histogram("span.sync.bind.place").count - spans_before == 6

        spans = [e["args"] for e in tracer.events() if e.get("name") == "sync.bind.place"]
        assert [s["layout"] for s in spans] == ["default"] * 4 + ["row_major", "default"]
        d = bound.data
        assert [s["bytes"] for s in spans[:3]] == [
            d.indices.nbytes, d.values.nbytes, d.labels.nbytes]
    finally:
        trace_mod.configure(enabled=False)


# -- (d) compiled for the chip, without one --------------------------------------
# The TPU's compiler is installed here and compiles for a described v5e (no
# device, no time).  Only a fixture may describe the topology: the process
# that does so holds libtpu's lock until it exits.

@pytest.fixture(scope="module")
def v5e():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: nothing to hold
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _resident_copies(v5e, rows, width, stored_width):
    """Copies of the whole resident values array in the epoch program
    compiled for one v5e chip, with the values stored `stored_width` wide."""
    import re

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_sgd_tpu.parallel.sync import BoundSync, ShardedData

    mesh = Mesh(np.array(v5e.devices[:1]), ("workers",))
    over_rows, everywhere = NamedSharding(mesh, P("workers")), NamedSharding(mesh, P())
    shape = jax.ShapeDtypeStruct
    data = ShardedData(shape((rows, 0), jnp.int32, sharding=over_rows),
                       shape((rows, stored_width), jnp.float32, sharding=over_rows),
                       shape((rows,), jnp.int32, sharding=over_rows), rows, width)
    bound = BoundSync(make_model("logistic", 1e-6, width, regularizer="l2"), mesh,
                      data, 100, 0.05, kernel="dense", virtual_workers=4)
    compiled = bound._epoch.lower(
        shape((width,), jnp.float32, sharding=everywhere), (), data.indices,
        data.values, data.labels, shape((2,), jnp.uint32, sharding=everywhere)).compile()
    stored = compiled.input_formats[0][3].layout.major_to_minor
    text = compiled.as_text()
    resident = re.escape(f"f32[{rows},") + r"\d+\]\S* copy\("
    # a whole array fetched into fast memory piecewise INSIDE the loop
    # (BoundSync._loop_labels: the labels, once a step)
    return stored, len(re.findall(resident, text)), text.count(" slice-start(")


def test_on_a_v5e_padded_rows_are_row_major_and_never_copied(v5e):
    rows, width = 491520, 2000  # epsilon-sync-1chip's train split
    assert lane_width((rows, width), "tpu") == 2048
    assert _resident_copies(v5e, rows, width, 2048) == ((0, 1), 0, 0)
    # what the rule is there for; the day this fails the compiler has
    # changed and mesh.ROW_MAJOR_MAX_PADDING can go
    assert _resident_copies(v5e, rows, width, width) == ((1, 0), 1, 0)


# -- (e) narrow sparse rows: indices and values packed into one stored row ------
# (parallel/mesh.py `packed_width` / `put_packed`, PERF.md section 6, PR 26)

@pytest.mark.parametrize("width,on_tpu", [
    (39, 128),    # criteo-logistic: 512 B a row where two 40-sublane arrays take 320
    (64, 128),    # the widest pair that fits 128 lanes
    (25, 128),    # 2 x the bytes of two 32-sublane arrays
    (11, 128),    # kdd2012-logistic: 4 x the bytes, measured (PR 30): 428.6 us a step against 444.0
    (9, 128),     # 128 <= 4.0 x 2 x 16
    (8, None),    # 128 > 4.0 x 2 x 8: eight times the bytes, not measured
    (65, None),   # two arrays of 65 do not fit one row
    (76, None),   # rcv1-hinge keeps its two arrays
    (0, None),    # dense rows have no indices to pack
])
def test_packing_rule_reads_only_width_and_platform(width, on_tpu):
    assert mesh_mod.packed_width(width, "tpu") == on_tpu
    assert mesh_mod.packed_width(width, "cpu") is None
    assert mesh_mod.PACKED_MAX_PADDING == 4.0


def _narrow(n=512, d=3000, p=39, seed=5):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, d, (n, p)).astype(np.int32)
    idx[:, 0] = 7  # one id in every row
    val = rng.normal(size=(n, p)).astype(np.float32)
    return Dataset(idx, val, rng.choice([-1, 1], n).astype(np.int32), d)


def _pack_everywhere(monkeypatch):
    from distributed_sgd_tpu.parallel import sync as sync_mod

    monkeypatch.setattr(sync_mod, "packed_width",
                        lambda width, platform: mesh_mod.packed_width(width, "tpu"))


@pytest.mark.parametrize("kernel", ["gather", "scalar", "mxu"])
def test_a_packed_binding_computes_what_two_arrays_compute(kernel, monkeypatch):
    data = _narrow()
    model = make_model("logistic", 1e-3, data.n_features, regularizer="l2")
    w = jnp.asarray(np.random.default_rng(6).normal(size=data.n_features).astype(np.float32))
    key = jax.random.PRNGKey(2)

    def run():
        bound = SyncEngine(model, make_mesh(2), 16, 0.1, kernel=kernel,
                           virtual_workers=2).bind(data)
        return bound, (np.asarray(bound.step(w, key)), np.asarray(bound.epoch(w, key)),
                       bound.evaluate(w), bound.predict(w))

    plain, want = run()
    before = metrics_mod.counter("bind.rows.packed").value
    _pack_everywhere(monkeypatch)
    packed, got = run()
    assert not plain.data.packed and packed.data.packed
    assert metrics_mod.counter("bind.rows.packed").value == before + 1
    assert packed.data.indices.shape == (512, 128) and packed.data.indices.dtype == jnp.int32
    assert packed.data.values.shape == (512, 0) and packed.data.width == 39
    stored = np.asarray(packed.data.indices)
    np.testing.assert_array_equal(stored[:, :39], data.indices)
    np.testing.assert_array_equal(stored[:, 39:78].view(np.float32), data.values)
    assert not stored[:, 78:].any()
    for a, b in zip(got[:2], want[:2]):
        np.testing.assert_array_equal(a, b)
    assert got[2] == want[2]
    np.testing.assert_array_equal(got[3], want[3])


def test_local_sgd_reads_packed_rows_too(monkeypatch):
    data = _narrow(seed=8)
    model = make_model("logistic", 1e-3, data.n_features, regularizer="l2")

    def fit():
        return LocalSGDEngine(model, make_mesh(2), 8, 0.1, sync_period=2,
                              check_every=8, seed=1).fit(data, data, max_epochs=1).weights

    want = fit()
    _pack_everywhere(monkeypatch)
    np.testing.assert_array_equal(fit(), want)


def test_on_a_v5e_packed_rows_are_row_major_never_copied_and_drawn_in_one_gather(v5e):
    import re

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_sgd_tpu.parallel.sync import BoundSync, ShardedData

    rows, width, d = 4096 * 64, 39, 1_000_000  # criteo-logistic's shape, fewer rows
    mesh = Mesh(np.array(v5e.devices[:1]), ("workers",))
    over_rows, everywhere = NamedSharding(mesh, P("workers")), NamedSharding(mesh, P())
    shape = jax.ShapeDtypeStruct

    def compiled(packed):
        data = ShardedData(
            shape((rows, 128 if packed else width), jnp.int32, sharding=over_rows),
            shape((rows, 0 if packed else width), jnp.float32, sharding=over_rows),
            shape((rows,), jnp.int32, sharding=over_rows), rows, width, packed)
        bound = BoundSync(make_model("logistic", 1e-7, d, regularizer="l2"), mesh, data,
                          100, 0.05, kernel="gather", virtual_workers=4)
        return bound._epoch.lower(
            shape((d,), jnp.float32, sharding=everywhere), (), data.indices, data.values,
            data.labels, shape((2,), jnp.uint32, sharding=everywhere)).compile()

    program = compiled(True)
    assert program.input_formats[0][2].layout.major_to_minor == (0, 1)
    text = program.as_text()
    assert not re.findall(re.escape(f"[{rows},") + r"\d+\]\S* copy\(", text)
    def draws(t):  # the gather fusions of the step's draw
        return sum(1 for line in t.split("\n")
                   if "kind=kCustom" in line and 'dsgd.draw/gather"' in line)

    # the packed rows and the labels; two arrays' rows and the labels
    assert (draws(text), draws(compiled(False).as_text())) == (2, 3)
    # the step's scatter keeps its scope in the compiled program (one
    # accumulator for the four virtual workers: models/linear.py grad_workers)
    assert 'dsgd.scatter/scatter-add"' in text and 'dsgd.margins/gather"' in text


# -- (f) K virtual workers: ONE flat gather for their margins --------------------
# (models/linear.py `grad_workers`, ops/mxu.py `lane_minor_rows`; PERF.md
# section 6, PR 27)

def _mxu_epoch_text(v5e, workers, batch, d=47_236):
    """The `mxu` epoch program of `rcv1-hinge`'s shape (`d` features),
    compiled for one v5e chip."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_sgd_tpu.parallel.sync import BoundSync, ShardedData

    rows, width = 4096 * 64, 76
    mesh = Mesh(np.array(v5e.devices[:1]), ("workers",))
    over_rows, everywhere = NamedSharding(mesh, P("workers")), NamedSharding(mesh, P())
    shape = jax.ShapeDtypeStruct
    data = ShardedData(shape((rows, width), jnp.int32, sharding=over_rows),
                       shape((rows, width), jnp.float32, sharding=over_rows),
                       shape((rows,), jnp.int32, sharding=over_rows), rows, width)
    model = make_model("hinge", 1e-5, d, dim_sparsity=jnp.ones((d,), jnp.float32))
    bound = BoundSync(model, mesh, data, batch, 0.5, kernel="mxu", virtual_workers=workers)
    return bound._epoch.lower(
        shape((d,), jnp.float32, sharding=everywhere), (), data.indices, data.values,
        data.labels, shape((2,), jnp.uint32, sharding=everywhere)).compile().as_text()


def _onehot_matmuls(v5e, workers, batch):
    """(scope, dim_labels, left operand's shape, its minor-most dimension)
    of every convolution in that program."""
    import re

    text = _mxu_epoch_text(v5e, workers, batch)
    stored = {name: (tuple(int(n) for n in dims.split(",")), int(minor))
              for name, dims, minor in re.findall(
                  r"^\s*(%\S+) = \w+\[([\d,]+)\]\{(\d+)", text, re.M)}
    return sorted(
        (scope, labels) + stored[left] for left, labels, scope in re.findall(
            r" convolution\((%[^,]+), [^)]*\).*?dim_labels=(\S+?),.*?op_name=\"[^\"]*"
            r"(dsgd\.[a-z]+)", text))


@pytest.mark.parametrize("batch,gathered_rows,shards", [(100, 448, 1), (200, 800, 2)])
def test_on_a_v5e_the_virtual_workers_margins_are_one_flat_gather(
        v5e, batch, gathered_rows, shards):
    k, entries, r = 4, batch * 76, 376
    assert mxu_mod.lane_minor_rows(k * batch, 76) == gathered_rows
    assert mxu_mod.scatter_shards(entries, r) == shards
    # the gather: ONE [T, R] x [R, 128] with no worker dimension, its one-hot
    # operand built with the entries along the lanes (dimension 0 minor: what
    # `lane_minor_rows` pads 400 rows to 448 for; the day this fails the
    # compiler has changed and the rule's constants can go); the scatter
    # keeps its workers apart ('dim_sparsity' masks each reply by its
    # worker's own support) and, at batch 200, carries the shard axis
    # beside them, the sum over the shards inside the convolution
    apart = [("dsgd.scatter", "0fb_0io->0bf", (k, entries, r), 1),
             ("dsgd.scatter", "01fb_01io->0bf1", (k, 2, entries // 2, r), 2)][shards - 1]
    assert _onehot_matmuls(v5e, k, batch) == [
        ("dsgd.margins", "bf_io->bf", (gathered_rows * 76, r), 0), apart]
    # one worker a device never goes through grad_workers: two plain
    # matmuls, entries-major as they were (the scatter in two shards at
    # batch 200)
    alone = [("dsgd.scatter", "fb_io->bf", (entries, r), 1),
             ("dsgd.scatter", "0fb_0io->bf0", (2, entries // 2, r), 1)][shards - 1]
    assert _onehot_matmuls(v5e, 1, batch) == [
        ("dsgd.margins", "bf_io->bf", (entries, r), 1), alone]


@pytest.mark.parametrize("rows,width,runs_on", [
    (400, 76, 448),    # rcv1-sync-1chip: 34,048 entries, 266 whole lanes
    (800, 76, 800),    # rcv1-sync-b200: 60,800 = 475 lanes as it comes
    (512, 76, 512),    # the evaluation's piece
    (600, 76, 608),    # whole lanes cost 1.3 % here
    (300, 76, 300),    # 448 rows would be 1.49 x the entries
    (100, 76, 100),    # one worker's batch
    (400, 39, 400),    # 15,600 entries: 32,768 is out of reach
    (16, 6, 16),
    (280, 128, 280),   # 128-wide rows are whole lanes at any count
])
def test_matvec_pads_to_whole_lanes_only_where_an_eighth_more_entries_buys_them(
        rows, width, runs_on):
    assert mxu_mod.lane_minor_rows(rows, width) == runs_on
    assert (mxu_mod.LANE_MINOR_MIN_ENTRIES, mxu_mod.MATVEC_MAX_PADDING) == (32_768, 1.125)


# -- (g) the scatter's contraction stays inside one of the compiler's windows -----
# (ops/mxu.py `scatter_shards`; PERF.md section 6, PR 29)

def _scatter_windows(text):
    """(entries a window, iterations) of the scatter's convolution fusion: the
    compiler walks the contraction in windows of so many sublane tiles of 8
    entries, one pipeline iteration a window."""
    import re

    (window, iterations), = re.findall(
        r'dsgd\.scatter\)?/dot_general".*?"kernel_window_bounds":\[([^\]]+)\].*?'
        r'"iteration_bounds":\[([^\]]+)\]', text)
    count = lambda bounds: int(np.prod([int(n.strip('"')) for n in bounds.split(",")]))
    return 8 * count(window), count(iterations)


@pytest.mark.parametrize("workers,batch,d,shards,window,iterations", [
    (4, 100, 47_236, 1, 7_600, 4),     # rcv1-sync-1chip: a worker's contraction whole
    (4, 110, 47_236, 1, 8_360, 4),     # SCATTER_WINDOW_ENTRIES: one window still
    (4, 200, 47_236, 2, 7_600, 8),     # rcv1-sync-b200: a window a shard
    (1, 100, 47_236, 1, 7_600, 1),     # rcv1-sync-4chip, Hogwild's kstep
    (1, 400, 47_236, 4, 7_600, 4),
    (4, 100, 200_000, 4, 1_904, 16),   # R = 1,568: the window holds fewer entries
])
def test_on_a_v5e_every_scatter_contraction_is_one_window(
        v5e, workers, batch, d, shards, window, iterations):
    assert mxu_mod.scatter_shards(batch * 76, mxu_mod.n_blocks(d)) == shards
    assert _scatter_windows(_mxu_epoch_text(v5e, workers, batch, d)) == (window, iterations)


def test_on_a_v5e_one_deeper_dot_is_tiled_by_128_entries(v5e, monkeypatch):
    # what the rule is there for: `rcv1-sync-b200`'s 15,200-deep contraction as
    # ONE dot runs in 4 x 119 windows of 128 entries, each paying a window's
    # fixed cost (178.9 us a step against 54.75 in two shards); the day this
    # fails the compiler has changed and mxu.SCATTER_WINDOW_ENTRIES can go
    monkeypatch.setattr(mxu_mod, "scatter_shards", lambda entries, rows: 1)
    assert _scatter_windows(_mxu_epoch_text(v5e, 4, 200)) == (128, 476)
    # and from the first batch past the constant's margin on
    assert _scatter_windows(_mxu_epoch_text(v5e, 4, 115)) == (128, 276)


# -- (h) a step whose device bytes have no term in the feature count --------------
# (parallel/sync.py `_sparse_step`, ops/kernels.py `sparse_update`; PERF.md
# section 6, PR 30)

def _kdd2012_epoch(v5e, devices, packed):
    """The epoch program of `kdd2012-sync-1chip`'s shape (6,488,064 rows a
    device of 11 entries, D = 54,686,452) compiled for `devices` v5e chips."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_sgd_tpu.parallel.sync import BoundSync, ShardedData

    rows, width, d = 6_488_064 * devices, 11, 54_686_452
    mesh = Mesh(np.array(v5e.devices[:devices]), ("workers",))
    over_rows, everywhere = NamedSharding(mesh, P("workers")), NamedSharding(mesh, P())
    shape = jax.ShapeDtypeStruct
    data = ShardedData(
        shape((rows, 128 if packed else width), jnp.int32, sharding=over_rows),
        shape((rows, 0 if packed else width), jnp.float32, sharding=over_rows),
        shape((rows,), jnp.int32, sharding=over_rows), rows, width, packed)
    bound = BoundSync(make_model("logistic", 1.0 / 6_488_064, d, regularizer="l2"), mesh,
                      data, 100, 0.1, kernel="gather", virtual_workers=4 // devices)
    assert bound.plan.update == "sparse" and bound.steps_per_epoch == {1: 16_221, 4: 64_881}[devices]
    return bound._epoch.lower(
        shape((d,), jnp.float32, sharding=everywhere), (), data.indices, data.values,
        data.labels, shape((2,), jnp.uint32, sharding=everywhere)).compile()


def _loop_bodies(text):
    """{computation name: its lines} of every while loop's body."""
    import re

    bodies = set(re.findall(r" while\(.*?body=(%[\w.\-]+)", text))
    out, name = {}, None
    for line in text.split("\n"):
        head = re.match(r"(%[\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1) if head.group(1) in bodies else None
        elif name is not None and " = " in line:
            out.setdefault(name, []).append(line)
    return out


@pytest.mark.parametrize("devices,packed", [(1, True), (1, False), (4, True)])
def test_on_a_v5e_no_step_of_the_sparse_epoch_passes_over_the_weights(v5e, devices, packed):
    import re

    program = _kdd2012_epoch(v5e, devices, packed)
    r = mxu_mod.n_blocks(54_686_452)
    w_bytes = r * 128 * 4
    assert (r, w_bytes) == (427_240, 218_746_880)
    whole = re.compile(rf"= f32\[(?:{r},128|{r * 128})\]")
    bodies = _loop_bodies(program.as_text())
    assert bodies
    made = [line for lines in bodies.values() for line in lines
            if whole.search(line) and not re.search(
                r"\]\S* (?:bitcast|get-tuple-element|parameter)\(", line)]
    # inside the scan's body ONE operation's result is as large as w: the
    # kernel that writes the step's touched rows into the carry by its own
    # DMAs (ops/gather.py `_write_rows`, PR 31), its output aliased to the
    # carry.  No zero-fill, no regulariser pass, no `w - lr * g`, no copy:
    # the row gathers read the carry where it lies
    assert len(made) == 1, made
    assert 'custom_call_target="tpu_custom_call"' in made[0]
    assert 'dsgd.scatter/scatter_rows' in made[0]
    assert re.search(r'output_to_operand_aliasing=\{\{\}: \(\d, \{\}\)\}', made[0])
    # everything the scatter is made of is filed under its scope: the sort
    # by id, the two products of the sum by row, the sort that puts the
    # written rows' positions first, the kernel
    body = [line for lines in bodies.values() for line in lines]
    mine = [line for line in body if re.search(
        r" (?:sort|custom-call)\(|dot_general", line)
        and "AssumeGatherIndicesInBound" not in line]
    assert len(mine) >= 5 and all("dsgd.scatter/" in line for line in mine), mine
    assert sum(" sort(" in line for line in mine) == 2
    assert not any(re.search(r"\]\S* copy\(", line) and whole.search(line)
                   for lines in bodies.values() for line in lines)
    # and the program holds ONE w-sized temporary (the carry), not three
    # (a zeroed accumulator, the regularised gradient, the updated weights)
    memory = program.memory_analysis()
    assert w_bytes <= memory.temp_size_in_bytes < 1.01 * w_bytes
    text = program.as_text()
    # the entries cross the mesh as entries: ONE all-gather a step of ids and
    # updates side by side, never an all-reduce of a gradient
    assert text.count(" all-gather(") + text.count(" all-gather-start(") == int(devices > 1)
    assert " all-reduce(" not in text and " all-reduce-start(" not in text
    if devices > 1:
        assert re.search(r"s32\[4,2,1100\]\S* all-gather", text)
        assert 'dsgd.allreduce' in re.search(r"all-gather\(.*", text).group(0)
    # the fold, once a program, under its own scope
    assert "dsgd.rescale" in text


# -- (i) weights with an output axis: rows of outputs (PERF.md section 6, PR 32) -----

def _topics_programs(v5e, devices):
    """(bound, step text, evaluation lowering) of `rcv1-topics-hinge`'s shape
    compiled for `devices` v5e chips."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_sgd_tpu.parallel.sync import BoundSync, ShardedData

    d, c, rows, width = 47_236, 103, 4096 * 16 * devices, 76
    mesh = Mesh(np.array(v5e.devices[:devices]), ("workers",))
    over_rows, everywhere = NamedSharding(mesh, P("workers")), NamedSharding(mesh, P())
    shape = jax.ShapeDtypeStruct
    data = ShardedData(shape((rows, width), jnp.int32, sharding=over_rows),
                       shape((rows, width), jnp.float32, sharding=over_rows),
                       shape((rows, 128), jnp.int8, sharding=over_rows), rows, width)
    model = make_model("hinge", 1.7e-7, d, regularizer="l2", n_outputs=c)
    bound = BoundSync(model, mesh, data, 100, 0.25, kernel="gather",
                      virtual_workers=4 // devices)
    w = shape((d, c), jnp.float32, sharding=everywhere)
    step = bound._step.lower(w, (), data.indices, data.values, data.labels,
                             shape((2,), jnp.uint32, sharding=everywhere)).compile().as_text()
    return bound, step, bound._eval.lower(w, data.indices, data.values, data.labels)


def _kernels_of(text):
    """The lines of a compiled program that call a kernel of ours."""
    return [line for line in text.split("\n") if " custom-call(" in line
            and 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("devices", [1, 4])
def test_on_a_v5e_a_step_with_outputs_gathers_and_writes_whole_rows(v5e, devices):
    """`rcv1-topics-hinge`'s programs compile for the chip (nothing ran):
    the update is ONE kernel a step, the merge pass over the carry's
    128-lane rows of OUTPUTS (`kernels.merges_scatter`: 47,236 rows against
    30,400 entries), the entries cross the mesh as factors in ONE
    all-gather, and the evaluation's gathered rows split into [P, B, L]
    where they lie."""
    import re

    bound, step, evaluation = _topics_programs(v5e, devices)
    assert (bound.plan.update, bound.plan.scatter) == ("sparse", "merge")
    kernel = _kernels_of(step)
    assert len(kernel) == 1 and "f32[47240,128]" in kernel[0]
    assert "dsgd.scatter/scatter_merge" in kernel[0]
    # in place on the carry: the kernel's weights operand is its result
    assert "output_to_operand_aliasing={{}: (6, {})}" in kernel[0]
    # ONE collective a step, of the entries' factors and the samples'
    # coefficient rows as one vector of bits (the compiler runs a 1-D
    # all-gather as an all-reduce of zero-padded pieces), never of a gradient
    crossing = re.findall(r"= (\S+) all-(?:gather|reduce)(?:-start)?\(", step)
    assert len(crossing) == int(devices > 1), crossing
    assert all(c.startswith("s32[142400]") for c in crossing), crossing
    evaluation = evaluation.compile().as_text()
    assert "f32[311296,128]" in evaluation  # a chunk's 4,096 x 76 gathered rows
    # entry-major: no [B, P, L] form of them, which was a copy of all of them
    assert "f32[4096,76,128]" not in evaluation


@pytest.mark.parametrize("shape,ending", [("topics", "scatter_merge"), ("kdd2012", "scatter_rows")])
def test_on_a_v5e_the_merge_pass_and_the_words_keep_their_endings(v5e, shape, ending):
    """The walk of the sorted factors (`scatter_runs`, PR 37) took the DMA
    ending of an output axis's rows and no other: `rcv1-topics-hinge`'s step
    still ends in the merge pass and `kdd2012-logistic`'s words in a DMA a
    touched row, one kernel a step each."""
    if shape == "topics":
        bound, text, _ = _topics_programs(v5e, 1)
        assert bound.plan.scatter == "merge"
    else:
        text = _kdd2012_epoch(v5e, 1, True).as_text()
    kernel = _kernels_of(text)
    assert len(kernel) == 1 and f"dsgd.scatter/{ending}" in kernel[0]
    assert "scatter_runs" not in text
    assert "disable_bounds_checks" not in kernel[0]  # only the wide-row kernels' DMAs


def test_on_a_v5e_wide_rows_are_tiles_a_dma_can_name_and_lists_are_expanded(v5e):
    """`amazoncat13k-dismec`'s programs compile for the chip (nothing ran):
    1,000 outputs in eight lane groups, `W` carried as tiles
    `[203,888, 8, 128]` (`gather.to_tiles`: a feature's 4 KB contiguous), the
    update ONE kernel a step (`scatter_runs`: the walk of the sorted factors,
    PR 37) and nothing of a step's 28,800 entries x 4 KB outside the margins'
    gather, the label lists expanded in the step and in the evaluation, whose
    chunk's row gather runs in pieces of 512 samples (`kernels.margin_rows`).
    And what the tiles are there for: on `[D', 1,024]` the chip's compiler
    refuses the kernel's row DMAs (the day the second half fails, the
    compiler has changed and the tiles can go)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_sgd_tpu.ops import gather
    from distributed_sgd_tpu.parallel.sync import BoundSync, ShardedData

    d, c, rows, width = 203_882, 1_000, 4096 * 16, 72
    mesh = Mesh(np.array(v5e.devices[:1]), ("workers",))
    over_rows, everywhere = NamedSharding(mesh, P("workers")), NamedSharding(mesh, P())
    shape = jax.ShapeDtypeStruct
    data = ShardedData(shape((rows, width), jnp.int32, sharding=over_rows),
                       shape((rows, width), jnp.float32, sharding=over_rows),
                       shape((rows, 8), jnp.int32, sharding=over_rows), rows, width,
                       label_lists=True)
    model = make_model("squared_hinge", 8.4e-7, d, regularizer="l2", n_outputs=c)

    def endings():
        return [metrics_mod.counter(f"bind.scatter.{ending}").value
                for ending in ("runs", "merge", "rows")]

    runs, merge, dma = endings()
    bound = BoundSync(model, mesh, data, 100, 0.1, kernel="gather", virtual_workers=4)
    # once a binding, and no other ending (the plan itself: tests/test_kernel_plan.py)
    assert endings() == [runs + 1, merge, dma]
    w = shape((d, c), jnp.float32, sharding=everywhere)
    step = bound._step.lower(w, (), data.indices, data.values, data.labels,
                             shape((2,), jnp.uint32, sharding=everywhere)).compile().as_text()
    kernel = _kernels_of(step)
    assert len(kernel) == 2 and all("f32[203888,8,128]" in k for k in kernel)
    assert "dsgd.margins/margin_tiles" in kernel[0] and "dsgd.labels" in step
    assert "dsgd.scatter/scatter_runs" in kernel[1]
    # their DMAs start unchecked, the ids put in range before the call
    assert all('"disable_bounds_checks":true' in k for k in kernel)
    # no array of a row an entry at all (the margins fetch each distinct
    # tile once, PR 40); the benchmark counts a window's steps by its most
    # frequent operation, so no loop of XLA's may turn inside a step
    wide = [line for line in step.split("\n") if re.search(r"= f32\[28800,(1024|8,128)\]", line)]
    assert not wide, wide[:3]
    assert " while(" not in step
    evaluation = bound._eval.lower(w, data.indices, data.values, data.labels,
                                   bound.margin_plan).compile().as_text()
    kernel = _kernels_of(evaluation)
    assert len(kernel) == 1 and "dsgd.margins/margin_tiles" in kernel[0]
    assert "f32[4096,8,128]" in kernel[0]  # a chunk's margins, 16 pieces of 256 samples
    assert '"disable_bounds_checks":true' in kernel[0]
    assert not re.search(r"f32\[(36864|294912),8,128\]", evaluation) and "dsgd.labels" in evaluation
    # the same kernel on rows of eight lane groups that are NOT tiles
    flat = shape((2048, 1024), jnp.float32, sharding=everywhere)
    entries = (shape((256,), jnp.int32, sharding=everywhere),
               shape((256,), jnp.float32, sharding=everywhere),
               shape((256,), jnp.int32, sharding=everywhere),
               shape((400, 1024), jnp.float32, sharding=everywhere))
    with pytest.raises(Exception, match="aligned to tiling"):
        jax.jit(lambda w2, *e: gather.scatter_rows_into(w2, *e, "runs")).lower(
            flat, *entries).compile()


@pytest.mark.parametrize("d,outputs,model,margin_tiles", [
    (4096, 1000, "squared_hinge", True),  # tiles of eight lane groups: the margin kernel
    (47_236, 103, "hinge", False),        # [D', 128] rows under the merge pass
    (4_000_000, 1, "logistic", False),    # flat w, kdd2012-logistic's form
])
def test_on_a_v5e_only_tiles_take_their_margins_from_the_margin_kernel(
        v5e, d, outputs, model, margin_tiles):
    """Bindings small enough for tier-1 compiled for a described v5e: on
    tiles the step's and the evaluation's margins are ONE custom call
    `margin_tiles` each, under `dsgd.margins` (what `margins_us_per_step`
    and `eval_margins_ms` read), and the binding is counted once under
    `bind.margins.tiles` and once under `bind.margins.planned`: the
    evaluation reads the margin plan made at bind and sorts nothing, the
    epoch program sorts every step's fresh draw for its kernel as before.
    On 128-lane rows and on flat `w` no such call exists, no plan is made
    and XLA's gather stays."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_sgd_tpu.ops import kernels
    from distributed_sgd_tpu.parallel.sync import BoundSync, ShardedData

    rows, width = 4096, 6  # (the sparse step: from 4e6 words of weights on)
    mesh = Mesh(np.array(v5e.devices[:1]), ("workers",))
    over_rows, everywhere = NamedSharding(mesh, P("workers")), NamedSharding(mesh, P())
    shape = jax.ShapeDtypeStruct
    labels = (rows, 8) if outputs > 1 else (rows,)
    data = ShardedData(shape((rows, width), jnp.int32, sharding=over_rows),
                       shape((rows, width), jnp.float32, sharding=over_rows),
                       shape(labels, jnp.int32, sharding=over_rows), rows, width,
                       label_lists=outputs > 1)
    counters = [metrics_mod.counter(f"bind.margins.{name}") for name in ("tiles", "planned")]
    before = [counter.value for counter in counters]
    bound = BoundSync(make_model(model, 1e-6, d, regularizer="l2", n_outputs=outputs), mesh,
                      data, 100, 0.1, kernel="gather", virtual_workers=4)
    assert bound.plan.update == "sparse"
    assert [counter.value for counter in counters] == [b + margin_tiles for b in before]
    assert bound.plan.eval_fetch.how == ("planned" if margin_tiles else "gather")
    assert bound.plan.step_fetch.how == ("distinct" if margin_tiles else "gather")
    assert (bound.margin_plan is None) != margin_tiles
    w = shape((d, outputs) if outputs > 1 else (d,), jnp.float32, sharding=everywhere)
    epoch = bound._epoch.lower(w, (), data.indices, data.values, data.labels,
                               shape((2,), jnp.uint32, sharding=everywhere)).compile().as_text()
    evaluation = bound._eval.lower(w, data.indices, data.values, data.labels,
                                   *([bound.margin_plan] if margin_tiles else [])
                                   ).compile().as_text()
    piece = kernels.margin_tiles(4096, width, 1024)
    for text, samples in ((epoch, 400), (evaluation, piece)):
        called = [k for k in _kernels_of(text) if "margin_tiles" in k]
        assert len(called) == margin_tiles and " margin_tiles" not in text.replace(
            "dsgd.margins/margin_tiles", "")
        if margin_tiles:
            assert "dsgd.margins/margin_tiles" in called[0]
            assert f"f32[{4096 if text is evaluation else samples},8,128]" in called[0]
    if margin_tiles:
        # a step's draw is sorted for its kernel; the evaluation's chunk is
        # not: its pieces' plan came with the call
        step = bound.plan.step_fetch.piece
        sorts = [line for line in epoch.split("\n") if " sort(" in line]
        assert any(f"u32[{400 // step},{step * width}]" in line for line in sorts), sorts
        assert " sort(" not in evaluation


# -- (j) a row's label rides in a spare word of the stored row ----------------------
# (parallel/mesh.py `label_slot`, `BoundSync.draw_rows`; PERF.md section 6, PR 33)

@pytest.mark.parametrize("width,lanes,outputs,on_tpu", [
    (11, 128, 1, 22),       # kdd2012-logistic: 22 of 128 lanes used
    (39, 128, 1, 78),       # criteo-logistic
    (63, 128, 1, 126),      # the widest packed row with a lane to spare
    (64, 128, 1, None),     # indices and values fill the row
    (2000, None, 1, 2000),  # epsilon-logistic: the first of 48 padding lanes
    (120, None, 1, 120),
    (2048, None, 1, None),  # whole lanes: nothing is padded
    (76, None, 1, 76),      # rcv1-hinge, rows-minor: 77 of 80 sublanes
    (80, None, 1, None),    # whole sublane groups: a column more is a group more
    (112, None, 1, None),
    (76, None, 103, None),  # rcv1-topics-hinge: its label is a row of its own
    (39, 128, 2, None),
    (0, None, 1, None),
])
def test_the_labels_word_is_read_off_widths_outputs_and_platform(width, lanes, outputs, on_tpu):
    if lanes is not None:
        assert mesh_mod.packed_width(width, "tpu") == lanes
    assert mesh_mod.label_slot(width, lanes, outputs, "tpu") == on_tpu
    assert mesh_mod.label_slot(width, lanes, outputs, "cpu") is None


_PLACEMENTS = ("packed", "padded", "minor")


def _by_hand(data, devices, placement, riding):
    """(mesh, ShardedData) of `data` placed as `bind` places it on a TPU,
    built by hand so that the CPU runs it: `packed` one 128-lane row a row,
    `padded` both arrays zero-padded to 128 lanes, `minor` as they come
    (the values one column wider where the label rides)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_sgd_tpu.parallel.sync import ShardedData

    mesh = make_mesh(devices)
    over_rows = NamedSharding(mesh, P("workers"))
    n, width = data.values.shape
    label = data.labels if riding else None
    if placement == "packed":
        indices = mesh_mod.put_packed(data.indices, data.values, 128, over_rows, label=label)
        values = jax.device_put(np.zeros((n, 0), np.float32), over_rows)
    else:
        lanes = 128 if placement == "padded" else None
        indices = mesh_mod.put_rows(data.indices, over_rows, width=lanes)
        values = mesh_mod.put_rows(data.values, over_rows, width=lanes, label=label)
    slot = (2 * width if placement == "packed" else width) if riding else None
    return mesh, ShardedData(indices, values, jax.device_put(data.labels, over_rows), n,
                             width, placement == "packed", slot)


def _programs(model, mesh, sharded, workers, kernel="gather"):
    from distributed_sgd_tpu.parallel.sync import BoundSync

    bound = BoundSync(model, mesh, sharded, 8, 0.1, kernel=kernel, eval_chunk=32,
                      virtual_workers=workers, steps_per_epoch=5)
    w = jnp.asarray(np.random.default_rng(6).normal(
        size=model.n_features).astype(np.float32) * 0.1)
    key = jax.random.PRNGKey(2)
    return bound, (np.asarray(bound.step(w, key)), np.asarray(bound.epoch(w, key)),
                   np.asarray(bound.evaluate(w)), bound.predict(w))


@pytest.mark.parametrize("update", ["dense", "sparse"])
@pytest.mark.parametrize("workers", [1, 4])
@pytest.mark.parametrize("placement", _PLACEMENTS)
def test_a_label_in_the_row_is_the_gathered_label_bit_for_bit(
        placement, workers, update, monkeypatch):
    from distributed_sgd_tpu.ops import kernels

    if update == "sparse":
        monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 0)
    data = _narrow(n=256, p=11, seed=9)
    model = make_model("logistic", 1e-3, data.n_features, regularizer="l2")
    gathered, want = _programs(model, *_by_hand(data, 2, placement, False), workers)
    riding, got = _programs(model, *_by_hand(data, 2, placement, True), workers)
    assert (gathered.plan.labels, riding.plan.labels) == ("gathered", "in_row")
    assert gathered.plan.update == riding.plan.update == update
    # the word holds the label as float32, past everything a reader takes
    stored = np.asarray(riding.data.indices if placement == "packed" else riding.data.values)
    held = stored[:, riding.data.label_slot]
    np.testing.assert_array_equal(
        held.view(np.float32) if placement == "packed" else held,
        data.labels.astype(np.float32))
    np.testing.assert_array_equal(np.asarray(riding.data.labels), data.labels)
    for name, a, b in zip(("step", "epoch", "evaluate", "predict"), got, want):
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("workers", [1, 4])
@pytest.mark.parametrize("loss,labels", [("logistic", "int"), ("least_squares", "float")])
def test_dense_rows_carry_their_label_in_the_first_padding_lane(loss, labels, workers):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_sgd_tpu.parallel.sync import ShardedData

    rng = np.random.default_rng(4)
    x = rng.normal(size=(256, 24)).astype(np.float32)
    y = (rng.choice([-1, 1], 256).astype(np.int32) if labels == "int"
         else rng.normal(size=256).astype(np.float32))
    model = make_model(loss, 1e-3, 24, regularizer="l2")
    mesh = make_mesh(2)
    over_rows = NamedSharding(mesh, P("workers"))

    def sharded(riding):
        return ShardedData(
            jax.device_put(np.zeros((256, 0), np.int32), over_rows),
            mesh_mod.put_rows(x, over_rows, width=128, label=y if riding else None),
            jax.device_put(y, over_rows), 256, 24, False, 24 if riding else None)

    _, want = _programs(model, mesh, sharded(False), workers, kernel="dense")
    riding, got = _programs(model, mesh, sharded(True), workers, kernel="dense")
    np.testing.assert_array_equal(np.asarray(riding.data.values)[:, 24], y.astype(np.float32))
    for name, a, b in zip(("step", "epoch", "evaluate", "predict"), got, want):
        np.testing.assert_array_equal(a, b, err_msg=name)


def _draw_gathers(bound):
    """(gathers under `dsgd.draw`, the entry computation's parameter shapes)
    of a binding's epoch program, compiled here."""
    import re

    d = bound.data
    text = bound._epoch.lower(
        jnp.zeros((bound.model.n_features,), jnp.float32), bound._opt_state, d.indices,
        d.values, d.labels, jax.random.PRNGKey(0)).compile().as_text()
    entry = text[text.index("ENTRY "):]
    return (sum(1 for line in text.split("\n") if " gather(" in line and "dsgd.draw/" in line),
            re.findall(r"= (\w+\[[\d,]*\])\S* parameter\(", entry))


@pytest.mark.parametrize("placement,gathers", [("packed", 2), ("padded", 3), ("minor", 3)])
def test_the_epoch_program_of_a_riding_binding_gathers_no_label(placement, gathers):
    from distributed_sgd_tpu.parallel.sync import BoundSync

    data = _narrow(n=256, p=11, seed=9)
    model = make_model("logistic", 1e-3, data.n_features, regularizer="l2")

    def program(riding):
        mesh, sharded = _by_hand(data, 1, placement, riding)
        return _draw_gathers(BoundSync(model, mesh, sharded, 8, 0.1, kernel="gather",
                                       eval_chunk=32, virtual_workers=4))

    (before, took), (after, takes) = program(False), program(True)
    # one gather fewer a step, and the label array is no argument at all
    assert (before, after) == (gathers, gathers - 1)
    assert "s32[256]" in took and "s32[256]" not in takes


def _ride_everywhere(monkeypatch, placement="minor"):
    """`bind` as on a TPU: the label's word by the TPU's rule, the rows
    placed as `placement` says."""
    from distributed_sgd_tpu.parallel import sync as sync_mod

    monkeypatch.setattr(
        sync_mod, "label_slot",
        lambda width, lanes, outputs, platform: mesh_mod.label_slot(
            width, lanes, outputs, "tpu"))
    if placement == "packed":
        _pack_everywhere(monkeypatch)
    elif placement == "padded":
        _pad_everywhere(monkeypatch)


@pytest.mark.parametrize("placement", _PLACEMENTS)
def test_bind_writes_the_label_where_the_rule_says_and_counts_it(placement, monkeypatch):
    data = _narrow(n=512, p=11, seed=7)
    model = make_model("logistic", 1e-3, data.n_features, regularizer="l2")
    w = jnp.asarray(np.random.default_rng(6).normal(size=data.n_features).astype(np.float32))
    key = jax.random.PRNGKey(2)

    def run():
        bound = SyncEngine(model, make_mesh(2), 16, 0.1, kernel="gather",
                           virtual_workers=2).bind(data)
        return bound, (np.asarray(bound.step(w, key)), np.asarray(bound.epoch(w, key)),
                       np.asarray(bound.evaluate(w)), bound.predict(w))

    plain, want = run()
    assert plain.data.label_slot is None and plain.plan.labels == "gathered"
    counters = ("bind.labels.in_row", "bind.labels.gathered")
    before = [metrics_mod.counter(name).value for name in counters]
    _ride_everywhere(monkeypatch, placement)
    bound, got = run()
    assert [metrics_mod.counter(name).value for name in counters] == [before[0] + 1, before[1]]
    d = bound.data
    assert (d.label_slot, d.packed, d.indices.shape, d.values.shape, d.labels.shape) == {
        "packed": (22, True, (512, 128), (512, 0), (512,)),
        "padded": (11, False, (512, 128), (512, 128), (512,)),
        "minor": (11, False, (512, 11), (512, 12), (512,))}[placement]
    for name, a, b in zip(("step", "epoch", "evaluate", "predict"), got, want):
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("why,width,outputs,dtype", [
    ("an output axis", 11, 3, np.float32),
    ("whole sublane groups", 16, 1, np.float32),
    ("values of another width than a label's", 11, 1, np.float16),
])
def test_bind_leaves_the_label_an_array_where_no_word_is_spare(
        why, width, outputs, dtype, monkeypatch):
    _ride_everywhere(monkeypatch)
    rng = np.random.default_rng(3)
    labels = rng.choice([-1, 1], (64, outputs) if outputs > 1 else 64).astype(np.int8)
    data = Dataset(rng.integers(0, 500, (64, width)).astype(np.int32),
                   rng.normal(size=(64, width)).astype(dtype), labels, 500)
    model = make_model("hinge", 1e-3, 500, regularizer="l2", n_outputs=outputs)
    before = metrics_mod.counter("bind.labels.gathered").value
    bound = SyncEngine(model, make_mesh(2), 8, 0.1, kernel="scalar").bind(data)
    assert bound.data.label_slot is None and bound.plan.labels == "gathered", why
    assert bound.data.values.shape == (64, width)
    assert metrics_mod.counter("bind.labels.gathered").value == before + 1


@pytest.mark.parametrize("slot,outputs", [(10, 1), (12, 1), (11, 2)])
def test_a_label_slot_that_is_no_spare_word_is_refused(slot, outputs):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_sgd_tpu.parallel.sync import BoundSync, ShardedData

    mesh = make_mesh(1)
    over_rows = NamedSharding(mesh, P("workers"))
    labels = np.ones((64, outputs) if outputs > 1 else 64, np.int8)
    data = ShardedData(jax.device_put(np.zeros((64, 11), np.int32), over_rows),
                       jax.device_put(np.zeros((64, 12), np.float32), over_rows),
                       jax.device_put(labels, over_rows), 64, 11, False, slot)
    model = make_model("hinge", 1e-3, 500, regularizer="l2", n_outputs=outputs)
    with pytest.raises(ValueError, match="no spare word"):
        BoundSync(model, mesh, data, 8, 0.1, kernel="scalar")


@pytest.mark.parametrize("riding,said", [(True, "in_row"), (False, "gathered")])
def test_the_train_split_record_says_where_the_labels_lie(riding, said, caplog, monkeypatch):
    import logging

    from distributed_sgd_tpu.core.trainer import SyncTrainer

    if riding:
        _ride_everywhere(monkeypatch)
    data = _narrow(n=256, p=11, seed=2)
    model = make_model("logistic", 1e-3, data.n_features, regularizer="l2")
    with caplog.at_level(logging.INFO, logger="dsgd.trainer"):
        SyncTrainer(model, make_mesh(1), batch_size=16, learning_rate=0.1).fit(
            data, data, max_epochs=1)
    record = next(r.getMessage() for r in caplog.records
                  if r.getMessage().startswith("train split:"))
    assert f" labels={said} " in record and " outputs=1 " in record


@pytest.mark.parametrize("placement", _PLACEMENTS)
def test_local_sgd_draws_the_label_with_the_row_too(placement, monkeypatch):
    data = _narrow(n=256, p=11, seed=8)
    model = make_model("logistic", 1e-3, data.n_features, regularizer="l2")

    def fit():
        return LocalSGDEngine(model, make_mesh(2), 8, 0.1, sync_period=2,
                              check_every=8, seed=1).fit(data, data, max_epochs=1).weights

    want = fit()
    before = metrics_mod.counter("bind.labels.in_row").value
    _ride_everywhere(monkeypatch, placement)
    np.testing.assert_array_equal(fit(), want)
    assert metrics_mod.counter("bind.labels.in_row").value == before + 2  # train, test


@pytest.mark.parametrize("cell,rows,width,stored,slot,kernel,d,gathers", [
    # the row gathers the cell's step keeps; the ledger's `kCustom` label
    # gather (PR 32: 5.76 / 6.25 / 4.10 us a step) is the one that goes
    ("rcv1-sync-1chip", 4096 * 64, 76, (76, 77), 76, "mxu", 47_236, 2),
    ("kdd2012-sync-1chip", 4096 * 64, 11, (128, 0), 22, "gather", 54_686_452, 1),
    ("epsilon-sync-1chip", 4096 * 32, 2000, (0, 2048), 2000, "dense", 2000, 1),
])
def test_on_a_v5e_the_draw_of_a_riding_binding_is_its_row_gathers(
        v5e, cell, rows, width, stored, slot, kernel, d, gathers):
    import re

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_sgd_tpu.parallel.sync import BoundSync, ShardedData

    assert mesh_mod.label_slot(width, mesh_mod.packed_width(width, "tpu"), 1, "tpu") == slot
    mesh = Mesh(np.array(v5e.devices[:1]), ("workers",))
    over_rows, everywhere = NamedSharding(mesh, P("workers")), NamedSharding(mesh, P())
    shape = jax.ShapeDtypeStruct
    model = (make_model("hinge", 1e-5, d, dim_sparsity=jnp.ones((d,), jnp.float32))
             if kernel == "mxu" else make_model("logistic", 1e-7, d, regularizer="l2"))

    def draws(riding):
        values = stored[1] - (0 if riding or kernel != "mxu" else 1)
        data = ShardedData(shape((rows, stored[0]), jnp.int32, sharding=over_rows),
                           shape((rows, values), jnp.float32, sharding=over_rows),
                           shape((rows,), jnp.int32, sharding=over_rows), rows, width,
                           kernel == "gather", slot if riding else None)
        bound = BoundSync(model, mesh, data, 100, 0.1, kernel=kernel, virtual_workers=4)
        text = bound._epoch.lower(
            shape((d,), jnp.float32, sharding=everywhere), (), data.indices, data.values,
            data.labels, shape((2,), jnp.uint32, sharding=everywhere)).compile().as_text()
        fusions = [line for line in text.split("\n")
                   if "kind=kCustom" in line and 'dsgd.draw/gather"' in line]
        # a 1-D result is a gather of single words: the label's
        return len(fusions), sum(1 for line in fusions if re.search(r"= \w+\[\d+\]", line))

    assert (draws(False), draws(True)) == ((gathers + 1, 1), (gathers, 0)), cell
