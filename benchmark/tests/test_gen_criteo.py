"""The criteo-shaped generator: rows are a function of the seed alone, hold
exactly 39 entries of value 1/sqrt(39) with ids in range, lay out as the
program's 80/20 split cuts them in whole evaluation chunks, carry the
positive rate the configuration states, and put the few-valued fields'
ids into a hundred and more of a step's 400 rows."""

import json
import math
import os

import jax
import numpy as np
import pytest

from benchmark.gen import criteo_like
from benchmark.harness import ROOT
from distributed_sgd_tpu.data.rcv1 import Dataset, train_test_split
from distributed_sgd_tpu.parallel.sync import padded_layout


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", "criteo-logistic.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def problem(config):
    return criteo_like.generate(config["data"], 2600000007, jax.devices()[:1], rehearse=True)


def test_same_seed_same_rows_other_seed_other_labels(config, problem):
    spec, dev = config["data"], jax.devices()[:1]
    again = criteo_like.generate(spec, 2600000007, dev, rehearse=True)
    other = criteo_like.generate(spec, 2600000008, dev, rehearse=True)
    for a, b in ((problem.train, again.train), (problem.test, again.test)):
        assert np.array_equal(np.asarray(a.indices), np.asarray(b.indices))
        assert np.array_equal(np.asarray(a.labels), np.asarray(b.labels))
    assert not np.array_equal(np.asarray(problem.train.indices), np.asarray(other.train.indices))
    assert not np.array_equal(np.asarray(problem.train.labels), np.asarray(other.train.labels))


def test_a_seed_beyond_32_signed_bits_is_taken(config):
    p = criteo_like.generate(config["data"], 2**31 + 12345, jax.devices()[:1], rehearse=True)
    assert np.isfinite(np.asarray(p.train.values)).all()


def test_every_row_holds_39_entries_of_one_value_with_ids_in_range(config, problem):
    spec = config["data"]
    assert spec["nnz"] == 39 and spec["n_features"] == 1_000_000
    for split in (problem.train, problem.test):
        idx, val = np.asarray(split.indices), np.asarray(split.values)
        assert idx.shape[1] == val.shape[1] == 39 and idx.dtype == np.int32
        assert idx.min() >= 0 and idx.max() < spec["n_features"]
        np.testing.assert_allclose(val, 1.0 / math.sqrt(39), rtol=1e-7)
        np.testing.assert_allclose((val.astype(np.float64) ** 2).sum(1), 1.0, rtol=1e-6)
        assert set(np.unique(np.asarray(split.labels))) == {-1, 1}
    assert problem.dim_sparsity is None and problem.n_features == spec["n_features"]


def test_the_fields_are_the_configurations_and_the_ids_a_function_of_field_and_value(config):
    card = criteo_like.cardinalities(config["data"])
    assert len(card) == 39 and list(card[13:]) == config["data"]["categorical_cardinalities"]
    assert sorted(card[13:])[:4] == [3, 4, 10, 15] and card.max() == 10_131_227
    rank = np.array([[1, 1, 7], [1, 2, 7]], np.int32)
    ids = np.asarray(criteo_like.feature_ids(rank, 1_000_000))
    assert ids[0, 0] == ids[1, 0] and ids[0, 2] == ids[1, 2]  # same field, same value
    assert ids[0, 1] != ids[1, 1] and ids[0, 0] != ids[0, 1]  # another value, another field


def test_a_few_valued_field_puts_one_id_into_a_hundred_of_400_rows(config, problem):
    """What no other configuration's rows do to the scatter."""
    card = criteo_like.cardinalities(config["data"])
    idx = np.asarray(problem.train.indices)
    rng = np.random.default_rng(0)
    for field in np.flatnonzero(card <= 10):  # 3, 4, 9 and 10 values
        column = idx[rng.integers(0, len(idx), 400), field]
        values, counts = np.unique(column, return_counts=True)
        assert len(values) <= card[field]
        assert counts.max() >= 100, (field, card[field], counts.max())
    # and a large field still repeats its head: Zipf, not uniform
    big = int(np.argmax(card))
    head = np.unique(idx[:4000, big], return_counts=True)[1].max()
    assert 40 <= head <= 400  # P(rank 1) = ln 2 / ln(C + 1) = 4.3 %


def test_positive_rate_is_the_configurations(config, problem):
    want = config["data"]["positive_rate"]
    for split in (problem.train, problem.test):
        rate = float((np.asarray(split.labels) > 0).mean())
        assert abs(rate - want) < 0.03, rate
    z = criteo_like.positive_threshold(want, config["data"]["label_noise"])
    assert 0.8 < z < 0.9  # P(N > z) = 0.2: 0.2 * 0.9 + 0.8 * 0.1 = 0.26


def test_labels_follow_the_planted_separator(config, problem):
    from benchmark.gen.rcv1_like import planted_weight

    idx = np.asarray(problem.test.indices)
    w = np.asarray(planted_weight(idx, np.uint32(2600000007 & 0xFFFFFFFF)))
    margin = (w * np.asarray(problem.test.values)).sum(1)
    y = np.asarray(problem.test.labels)
    assert margin[y > 0].mean() > margin[y < 0].mean() + 0.5


def test_split_is_the_programs_and_bind_pads_nothing(config):
    spec = config["data"]
    for n_dev in (1, 4):
        p = criteo_like.generate(spec, 3, jax.devices()[:n_dev], rehearse=True)
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


def test_the_full_size_is_whole_evaluation_chunks_and_the_bytes_the_file_states(config):
    spec = config["data"]
    per_chip = spec["rows_per_chip"]
    assert per_chip == spec["block_rows"] * (spec["train_blocks"] + spec["test_blocks"])
    assert spec["block_rows"] % 4096 == 0 and spec["train_blocks"] == 4 * spec["test_blocks"]
    n_train = spec["block_rows"] * spec["train_blocks"]
    assert padded_layout(n_train, 1)[0] == n_train
    assert config["lam"] == pytest.approx(1.0 / n_train, rel=1e-12)
    # 324 B a row as the chip stores 39-wide rows (40 sublanes x 4 B x 2 arrays + label)
    assert 0.25 * 16.9e9 < per_chip * 324 < 0.30 * 16.9e9
    # one epoch of 4 workers x 100 rows
    assert math.ceil(math.ceil(n_train / 4) / 100) == 27_034  # ISSUE 26 wrote 27,033: 2,703,360 / 100 = 27,033.6, and the program takes the ceiling
