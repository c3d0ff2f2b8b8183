"""The kdd2012-shaped generator: rows are a function of the seed alone, hold
exactly 11 one-hot entries of value 1/sqrt(11), one a field and inside the
field's own id range, lay out as the program's 80/20 split cuts them in
whole evaluation chunks, carry the click rate the configuration states, and
put a 3-valued field's ids into a hundred and more of a step's 400 rows."""

import json
import math
import os

import jax
import numpy as np
import pytest

from benchmark.gen import kdd2012_like
from benchmark.harness import ROOT
from distributed_sgd_tpu.data.rcv1 import Dataset, train_test_split
from distributed_sgd_tpu.parallel.sync import padded_layout


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", "kdd2012-logistic.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def problem(config):
    return kdd2012_like.generate(config["data"], 3000000007, jax.devices()[:1], rehearse=True)


def test_same_seed_same_rows_other_seed_other_rows(config, problem):
    spec, dev = config["data"], jax.devices()[:1]
    again = kdd2012_like.generate(spec, 3000000007, dev, rehearse=True)
    other = kdd2012_like.generate(spec, 3000000008, dev, rehearse=True)
    for a, b in ((problem.train, again.train), (problem.test, again.test)):
        assert np.array_equal(np.asarray(a.indices), np.asarray(b.indices))
        assert np.array_equal(np.asarray(a.labels), np.asarray(b.labels))
    assert not np.array_equal(np.asarray(problem.train.indices), np.asarray(other.train.indices))
    assert not np.array_equal(np.asarray(problem.train.labels), np.asarray(other.train.labels))


def test_the_published_shape_is_never_cut(config):
    spec = config["data"]
    assert spec["n_features"] == 54_686_452 and spec["nnz"] == 11
    card = kdd2012_like.cardinalities(spec)
    assert len(card) == 11 and int(card.sum()) == 54_686_452
    assert sorted(card)[:3] == [3, 3, 3] and card.max() > 20_000_000
    assert config["model"] == "logistic" and config["regularizer"] == "l2"
    train_rows = spec["block_rows"] * spec["train_blocks"]
    assert config["lam"] == pytest.approx(1.0 / train_rows, rel=1e-12)


def test_every_row_holds_one_id_a_field_inside_the_fields_range(config, problem):
    card = kdd2012_like.cardinalities(config["data"])
    first = np.cumsum(card) - card
    for split in (problem.train, problem.test):
        idx, val = np.asarray(split.indices), np.asarray(split.values)
        assert idx.shape[1] == val.shape[1] == 11 and idx.dtype == np.int32
        assert (idx >= first[None, :]).all() and (idx < (first + card)[None, :]).all()
        np.testing.assert_allclose(val, 1.0 / math.sqrt(11), rtol=1e-7)
        assert set(np.unique(np.asarray(split.labels))) == {-1, 1}
    assert problem.dim_sparsity is None and problem.n_features == 54_686_452


def test_a_seed_beyond_32_signed_bits_is_taken(config):
    p = kdd2012_like.generate(config["data"], 2**31 + 12345, jax.devices()[:1], rehearse=True)
    assert np.isfinite(np.asarray(p.train.values)).all()


def test_a_three_valued_field_puts_one_id_into_a_hundred_of_400_rows(config, problem):
    card = kdd2012_like.cardinalities(config["data"])
    idx = np.asarray(problem.train.indices)
    rng = np.random.default_rng(0)
    for field in np.flatnonzero(card == 3):
        counts = np.unique(idx[rng.integers(0, len(idx), 400), field], return_counts=True)[1]
        assert len(counts) <= 3 and counts.max() >= 150  # P(rank 1) = ln 2 / ln 4 = 0.5
    # the largest field repeats its head (1/r draw) and still spreads wide
    big = int(np.argmax(card))
    values, counts = np.unique(idx[:4000, big], return_counts=True)
    assert 40 <= counts.max() <= 400 and len(values) > 2000


def test_click_rate_is_the_configurations(config, problem):
    want = config["data"]["positive_rate"]
    for split in (problem.train, problem.test):
        rate = float((np.asarray(split.labels) > 0).mean())
        assert abs(rate - want) < 0.01, rate


def test_split_is_the_programs_and_bind_pads_nothing(config):
    spec = config["data"]
    for n_dev in (1, 4):
        p = kdd2012_like.generate(spec, 3, jax.devices()[:n_dev], rehearse=True)
        whole = Dataset(
            np.concatenate([np.asarray(p.train.indices), np.asarray(p.test.indices)]),
            np.concatenate([np.asarray(p.train.values), np.asarray(p.test.values)]),
            np.concatenate([np.asarray(p.train.labels), np.asarray(p.test.labels)]),
            p.n_features)
        train, test = train_test_split(whole)
        assert len(train) == len(p.train) and len(test) == len(p.test)
        assert np.array_equal(train.indices, np.asarray(p.train.indices))
        assert np.array_equal(test.labels, np.asarray(p.test.labels))
        for split in (p.train, p.test):
            assert padded_layout(len(split), n_dev)[0] == len(split)
