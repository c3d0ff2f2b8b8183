"""The one-hot scatter cuts its contraction into shards no deeper than the
compiler keeps in one window (`mxu.scatter_shards`, `OneHotBatch.scatter_add`;
PERF.md section 6, PR 29).

What the chip compiles it to is held by tests/test_row_placement.py (the
`v5e` fixture); here: the rule as a pure function, the sharded form against
the one dot and a float64 `np.add.at`, the lowered programs (batch 100: the
one plain dot, as before the rule; batch 200: the shard axis on the dot),
and the counter and the record that say when it engages.
"""

import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sgd_tpu.data.synthetic import rcv1_like
from distributed_sgd_tpu.models.linear import make_model
from distributed_sgd_tpu.ops import mxu
from distributed_sgd_tpu.ops.sparse import SparseBatch
from distributed_sgd_tpu.parallel.mesh import make_mesh
from distributed_sgd_tpu.parallel.sync import BoundSync, ShardedData, SyncEngine
from distributed_sgd_tpu.utils import metrics as metrics_mod

D, P = 47_236, 76  # rcv1-hinge: R = 376 blocked rows
R = mxu.n_blocks(D)

# benches/onehot_call_sweep.py's grid of entries a contraction
SWEEP = (3_800, 5_700, 7_600, 7_680, 8_960, 8_968, 9_500, 9_728, 11_400, 13_300,
         15_200, 15_360, 22_800, 30_400, 38_000, 77_824)


# -- (a) the rule ----------------------------------------------------------------

@pytest.mark.parametrize("entries,shards", [
    (100 * 76, 1),    # rcv1-sync-1chip, rcv1-sync-4chip, Hogwild's kstep
    (1 * 76, 1),
    (32 * 1, 1),
    (110 * 76, 1),    # 8,360: the deepest contraction measured in one window
    (111 * 76, 2),
    (200 * 76, 2),    # rcv1-sync-b200: two shards of 7,600
    (300 * 76, 3),
    (400 * 76, 4),
    (1024 * 76, 10),  # batch 1,024: 7,783 deep, six pad entries
])
def test_rule_at_the_named_shapes(entries, shards):
    assert mxu.scatter_shards(entries, R) == shards


def test_rule_is_monotone_and_keeps_every_shard_inside_one_window():
    said = [mxu.scatter_shards(t, R) for t in SWEEP]
    assert said == sorted(said)
    depth = mxu.scatter_depth(R)
    for t, s in zip(SWEEP, said):
        assert -(-t // s) <= depth          # no shard deeper than the window
        assert s == 1 or -(-t // (s - 1)) > depth  # and no shard more than that takes


@pytest.mark.parametrize("n_rows,depth,b100,b200", [
    (8, 8_360, 1, 2), (128, 8_360, 1, 2), (376, 8_360, 1, 2),  # a window's entries
    (752, 4_180, 2, 4),      # beyond 376 rows: its one-hot elements
    (1_568, 2_004, 4, 8),    # D = 200,000: shards of 1,900, as the chip had them fastest
    (7_816, 402, 19, 38),    # D = 1,000,000 (only where 'mxu' is forced)
])
def test_the_window_shrinks_with_the_blocked_rows(n_rows, depth, b100, b200):
    assert mxu.scatter_depth(n_rows) == depth
    assert (mxu.scatter_shards(100 * P, n_rows), mxu.scatter_shards(200 * P, n_rows)) == (b100, b200)
    assert (mxu.SCATTER_WINDOW_ENTRIES, mxu.SCATTER_WINDOW_ROWS) == (8_360, 376)


# -- (b) the sharded form computes what the one dot computes ---------------------

def _one_dot(oh, coeff):
    """`OneHotBatch.scatter_add` as it stood before the rule, word for word:
    ONE dot over all T entries."""
    with jax.named_scope("dsgd.scatter"):
        cv = (
            oh.values.reshape(oh.batch_size, oh.pad_width)
            * coeff.astype(jnp.float32)[:, None]
        ).reshape(-1)
        contrib = oh.ohc.astype(jnp.float32) * cv[:, None]  # [T, L]
        return jax.lax.dot(
            oh.ohr.T, contrib.astype(oh.ohr.dtype),
            preferred_element_type=jnp.float32
        )


def _batch(b, p, d, trap, seed=29):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, d, (b, p)).astype(np.int32)
    if trap == "duplicates" and p > 1:
        idx[:, 1] = idx[:, 0]   # twice in one row
        idx[:, -1] = 0          # on top of where a pad entry lands
    elif trap == "one_index":
        idx[:] = d - 1
    val = rng.normal(size=(b, p)).astype(np.float32)
    coeff = rng.normal(size=b).astype(np.float32)
    return SparseBatch(jnp.asarray(idx), jnp.asarray(val)), jnp.asarray(coeff)


@pytest.mark.parametrize("trap", ["plain", "duplicates", "one_index"])
@pytest.mark.parametrize("shards", [2, 3, 4, 8])
@pytest.mark.parametrize("bp", [(12, 4), (7, 5), (1, 9), (13, 1), (64, 3)])
def test_sharded_scatter_equals_the_one_dot_and_float64(monkeypatch, bp, shards, trap):
    (b, p), d = bp, 300
    batch, coeff = _batch(b, p, d, trap)
    n_rows = mxu.n_blocks(d)
    monkeypatch.setattr(mxu, "scatter_shards", lambda t, r: shards)
    got = np.asarray(mxu.scatter_add(batch, coeff, n_rows))
    np.testing.assert_allclose(
        got, np.asarray(_one_dot(mxu.OneHotBatch(batch, n_rows), coeff)), rtol=1e-5, atol=1e-5)
    want = np.zeros(n_rows * mxu.LANES, np.float64)
    np.add.at(want, np.asarray(batch.indices).reshape(-1),
              (np.asarray(batch.values, np.float64)
               * np.asarray(coeff, np.float64)[:, None]).reshape(-1))
    np.testing.assert_allclose(got.reshape(-1), want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("regularizer", ["dim_sparsity", "l2"])
@pytest.mark.parametrize("k", [1, 3])
def test_sharded_replies_under_vmap_are_the_workers_own(monkeypatch, k, regularizer):
    d, b, p = 1_025, 9, 5
    rng = np.random.default_rng(3)
    made = [_batch(b, p, d, "duplicates", seed=j) for j in range(k)]
    ys = [jnp.asarray(rng.choice([-1, 1], b).astype(np.int32)) for _ in range(k)]
    w = jnp.asarray(rng.normal(size=d).astype(np.float32))
    model = make_model("hinge", 1e-3, d, regularizer=regularizer,
                       dim_sparsity=rng.random(d).astype(np.float32))
    wl = model.to_layout(w, "mxu")

    def reply():
        if k == 1:
            return model.grad(wl, made[0][0], ys[0], kernel="mxu")
        return jax.jit(lambda *a: model.grad_workers(*a, kernel="mxu"))(
            wl, jnp.stack([m[0].indices for m in made]),
            jnp.stack([m[0].values for m in made]), jnp.stack(ys))

    want = np.asarray(reply())
    monkeypatch.setattr(mxu, "scatter_shards", lambda t, r: 4)  # 45 entries: 3 pads
    np.testing.assert_allclose(np.asarray(reply()), want, rtol=1e-5, atol=1e-6)


# -- (c) what lowers ---------------------------------------------------------------

def _lowered(workers, devices, batch, program):
    """StableHLO of the `mxu` epoch / step program at `rcv1-hinge`'s shape."""
    from jax.sharding import NamedSharding, PartitionSpec as Spec

    rows = 4096 * devices
    mesh = make_mesh(devices)
    over_rows, everywhere = NamedSharding(mesh, Spec("workers")), NamedSharding(mesh, Spec())
    shape = jax.ShapeDtypeStruct
    data = ShardedData(shape((rows, P), jnp.int32, sharding=over_rows),
                       shape((rows, P), jnp.float32, sharding=over_rows),
                       shape((rows,), jnp.int32, sharding=over_rows), rows, P)
    model = make_model("hinge", 1e-5, D, dim_sparsity=jnp.ones((D,), jnp.float32))
    bound = BoundSync(model, mesh, data, batch, 0.5, kernel="mxu", virtual_workers=workers)
    return getattr(bound, program).lower(
        shape((D,), jnp.float32, sharding=everywhere), (), data.indices, data.values,
        data.labels, shape((2,), jnp.uint32, sharding=everywhere)).as_text()


def _scatter_dots(text):
    """(batching dims, left operand's shape) of every dot whose left operand
    is a one-hot over the R blocked rows with the entries contracted."""
    dots = re.findall(
        r"stablehlo\.dot_general .*?batching_dims = \[([\d, ]*)\] x \[[\d, ]*\], "
        r"contracting_dims = \[(\d+)\] x \[\d+\].*?: \(tensor<([\dx]+)xf32>", text)
    found = []
    for batching, contracting, dims in dots:
        dims = tuple(int(n) for n in dims.split("x"))
        if dims[-1] == R and int(contracting) == len(dims) - 2:
            found.append((len(batching.split(",")) if batching else 0, dims))
    return found


@pytest.mark.parametrize("program", ["_epoch", "_step"])
@pytest.mark.parametrize("workers,devices", [(4, 1), (1, 4)])
def test_batch_100_lowers_to_the_one_plain_dot(monkeypatch, workers, devices, program):
    said = _lowered(workers, devices, 100, program)
    # the form before the rule, word for word (locations are not printed)
    monkeypatch.setattr(mxu.OneHotBatch, "scatter_add", _one_dot)
    assert said == _lowered(workers, devices, 100, program)


@pytest.mark.parametrize("workers,devices,dot", [
    (4, 1, (2, (4, 2, 7_600, R))),  # batched over the workers and the shards
    (1, 4, (1, (2, 7_600, R))),
])
def test_batch_200_lowers_with_the_shard_axis_on_the_scatter(workers, devices, dot):
    assert _scatter_dots(_lowered(workers, devices, 200, "_epoch")) == [dot]
    assert _scatter_dots(_lowered(workers, devices, 100, "_epoch")) == []


# -- (d) the counter and the record ------------------------------------------------

def _rows(n=800):
    return rcv1_like(n, n_features=D, nnz=P, seed=3)


@pytest.mark.parametrize("kernel,batch,shards", [
    ("mxu", 200, 2),
    ("mxu", 100, 1),
    ("gather", 200, 1),   # the rule is the one-hot scatter's
    ("scalar", 200, 1),
])
def test_a_binding_counts_a_sharded_scatter_once(kernel, batch, shards):
    rows = _rows()
    model = make_model("hinge", 1e-5, D, regularizer="l2")
    counter = metrics_mod.counter("bind.scatter.sharded")
    before = counter.value
    bound = SyncEngine(model, make_mesh(1), batch, 0.5, kernel=kernel, eval_chunk=32,
                       virtual_workers=4).bind(rows)
    assert bound.plan.scatter_shards == shards
    assert counter.value - before == int(shards > 1)
    bound.step(jnp.zeros((D,), jnp.float32), jax.random.PRNGKey(0))
    assert counter.value - before == int(shards > 1)  # a binding, not a trace or a run


@pytest.mark.parametrize("batch,said", [(200, "scatter_shards=2"), (100, "scatter_shards=1")])
def test_the_train_split_record_says_the_scatters_shards(batch, said, caplog):
    from distributed_sgd_tpu.core.trainer import SyncTrainer

    rows = _rows()
    model = make_model("hinge", 1e-5, D, regularizer="l2")
    with caplog.at_level(logging.INFO, logger="dsgd.trainer"):
        SyncTrainer(model, make_mesh(1), batch, 0.5, virtual_workers=4).fit(
            rows, rows, max_epochs=1)
    record = next(r.getMessage() for r in caplog.records
                  if r.getMessage().startswith("train split:"))
    assert said in record and "kernel=mxu" in record
