"""The generators: the rows are a function of the seed alone, lay out as
the program's 80/20 split would cut them, need no padding at bind, and
carry the statistics the configuration files state."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.gen import epsilon_like, rcv1_like
from benchmark.harness import ROOT
from distributed_sgd_tpu.data.rcv1 import Dataset, dim_sparsity, train_test_split
from distributed_sgd_tpu.parallel.sync import padded_layout


def _spec(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)["data"]


GENS = [(rcv1_like, "rcv1-hinge"), (epsilon_like, "epsilon-logistic")]


@pytest.mark.parametrize("gen,config", GENS)
def test_same_seed_same_rows_other_seed_other_rows(gen, config):
    spec, dev = _spec(config), jax.devices()[:1]
    a = gen.generate(spec, 7, dev, rehearse=True)
    b = gen.generate(spec, 7, dev, rehearse=True)
    c = gen.generate(spec, 8, dev, rehearse=True)
    assert np.array_equal(np.asarray(a.train.values), np.asarray(b.train.values))
    assert np.array_equal(np.asarray(a.test.labels), np.asarray(b.test.labels))
    assert not np.array_equal(np.asarray(a.train.values), np.asarray(c.train.values))


@pytest.mark.parametrize("gen,config", GENS)
def test_split_is_the_programs_and_bind_pads_nothing(gen, config):
    spec = _spec(config)
    for n_dev in (1, 4):
        p = gen.generate(spec, 3, jax.devices()[:n_dev], rehearse=True)
        whole = Dataset(
            np.concatenate([np.asarray(p.train.indices), np.asarray(p.test.indices)]),
            np.concatenate([np.asarray(p.train.values), np.asarray(p.test.values)]),
            np.concatenate([np.asarray(p.train.labels), np.asarray(p.test.labels)]),
            p.n_features)
        train, test = train_test_split(whole)
        assert len(train) == len(p.train) and len(test) == len(p.test)
        assert np.array_equal(train.values, np.asarray(p.train.values))
        assert np.array_equal(test.labels, np.asarray(p.test.labels))
        for split in (p.train, p.test):
            assert padded_layout(len(split), n_dev)[0] == len(split)


def test_full_sizes_pad_nothing_on_one_and_four_chips():
    for config in ("rcv1-hinge", "epsilon-logistic"):
        spec = _spec(config)
        per_chip = spec["rows_per_chip"]
        assert per_chip == spec["block_rows"] * (spec["train_blocks"] + spec["test_blocks"])
        for chips in (1, 4):
            for blocks in (spec["train_blocks"], spec["test_blocks"]):
                n = chips * blocks * spec["block_rows"]
                assert padded_layout(n, chips)[0] == n


def test_rcv1_rows_do_not_depend_on_the_device_count():
    spec = _spec("rcv1-hinge")
    one = rcv1_like.generate(spec, 5, jax.devices()[:1], rehearse=True)
    four = rcv1_like.generate(spec, 5, jax.devices()[:4], rehearse=True)
    n = len(one.train)
    # device 0 of four generates the train blocks a single device generates first
    assert np.array_equal(np.asarray(one.train.indices),
                          np.asarray(four.train.indices[:n]))
    assert len(four.train) == 4 * n


def test_rcv1_statistics():
    spec = _spec("rcv1-hinge")
    p = rcv1_like.generate(spec, 11, jax.devices()[:1], rehearse=True)
    idx, val, y = (np.asarray(a) for a in (p.train.indices, p.train.values, p.train.labels))
    assert idx.shape[1] == 76 and idx.min() >= 0 and idx.max() < 47236
    assert np.all(np.diff(idx, axis=1) >= 0)  # sorted ids within a row
    np.testing.assert_allclose(np.linalg.norm(val, axis=1), 1.0, rtol=1e-5)
    assert set(np.unique(y)) == {-1, 1} and abs(y.mean()) < 0.05
    # repeat draws are zeroed: a stored feature occurs once per row
    live = val != 0
    assert not np.any(live[:, 1:] & (idx[:, 1:] == idx[:, :-1]))
    # the document frequency the generator states is the one the rows have
    counts = np.bincount(idx[live], minlength=47236)
    want = len(y) * rcv1_like.doc_prob(47236, 76)
    head = slice(0, 200)
    np.testing.assert_allclose(counts[head], want[head], rtol=0.12)
    # and its dim_sparsity is the program's, with expected counts for counted ones
    got = rcv1_like.dim_sparsity(47236, 76, len(y))
    np.testing.assert_allclose(got[head], dim_sparsity(Dataset(idx, val, y, 47236))[head],
                               rtol=0.12)


def test_planted_weight_is_standard_normal_and_seeded():
    ids = jnp.arange(47236)
    a = np.asarray(rcv1_like.planted_weight(ids, 1))
    b = np.asarray(rcv1_like.planted_weight(ids, 2))
    assert abs(a.mean()) < 0.02 and abs(a.std() - 1.0) < 0.02
    assert np.corrcoef(a, b)[0, 1] < 0.05
    assert np.array_equal(a, np.asarray(rcv1_like.planted_weight(ids, 1)))


def test_epsilon_statistics():
    spec = _spec("epsilon-logistic")
    p = epsilon_like.generate(spec, 13, jax.devices()[:1], rehearse=True)
    x, y = np.asarray(p.train.values), np.asarray(p.train.labels)
    assert p.train.is_dense and x.shape[1] == 2000 and p.dim_sparsity is None
    np.testing.assert_allclose(np.linalg.norm(x, axis=1), 1.0, rtol=1e-5)
    assert set(np.unique(y)) == {-1, 1} and abs(y.mean()) < 0.05


def test_layout_refuses_a_split_that_is_not_the_programs():
    from benchmark.gen.rows import layout

    spec = dict(_spec("rcv1-hinge"), train_blocks=7, test_blocks=3)
    with pytest.raises(ValueError):
        layout(spec, rehearse=False)
