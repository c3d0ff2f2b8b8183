"""`amazoncat_like`: `rcv1_like`'s rows seed for seed at D = 203,882 and
P = 72, a row's labels inside the DiSMEC batch as an ascending id list, the
batch a stratified sample of the power law the configuration states."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark.gen import amazoncat_like, rcv1_like
from benchmark.harness import ROOT
from distributed_sgd_tpu.parallel.sync import padded_layout


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs", "amazoncat13k-dismec.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def problem():
    return amazoncat_like.generate(_config()["data"], 7, jax.devices()[:1], rehearse=True)


def test_the_priors_are_a_power_law_that_sums_to_the_labels_a_point():
    spec = _config()["data"]
    p = amazoncat_like.all_priors(spec)
    assert p.shape == (13330,) and p[0] == spec["head_prior"] == 0.30
    np.testing.assert_allclose(p.sum(), 5.04, rtol=1e-9)
    rank = np.arange(1, 13331)
    beta = -np.log(p[1] / p[0]) / np.log(2.0)
    np.testing.assert_allclose(p, 0.30 * rank ** -beta, rtol=1e-9)  # a power law in rank
    assert 0.89 < beta < 0.90 and 6.0e-5 < p[-1] < 6.2e-5


def test_the_batch_is_one_rank_a_stratum_and_carries_its_share_of_the_mass():
    spec = _config()["data"]
    ranks = amazoncat_like.batch_ranks(spec)
    assert ranks.shape == (1000,) and np.all(np.diff(ranks) > 0)
    edges = np.round(np.linspace(0, 13330, 1001)).astype(int)
    assert np.all((edges[:-1] <= ranks) & (ranks < edges[1:]))  # one a stratum of 13-14 ranks
    held = amazoncat_like.priors(spec)
    assert held.shape == (1000,) and np.all(np.diff(held) < 0)
    # 5.04 x 1,000 / 13,330 = 0.378 positives a row in the batch
    np.testing.assert_allclose(held.sum(), 5.04 * 1000 / 13330, rtol=0.02)
    assert _config()["n_outputs"] == _config()["labels_held"] == spec["n_outputs"] == 1000


def test_rows_are_rcv1_likes_at_that_shape(problem):
    spec = _config()["data"]
    flat = dict(spec, label_noise=0.0)
    r = rcv1_like.generate(flat, 7, jax.devices()[:1], rehearse=True)
    for split in ("train", "test"):
        for field in ("indices", "values"):
            np.testing.assert_array_equal(np.asarray(getattr(getattr(problem, split), field)),
                                          np.asarray(getattr(getattr(r, split), field)))
    assert problem.n_features == 203882 and problem.train.values.shape == (4 * 4096, 72)
    assert problem.test.values.shape == (4096, 72) and problem.dim_sparsity is None
    assert spec["rows_per_chip"] == 1495040 == 5 * 73 * 4096
    for n_dev in (1, 4):  # whole evaluation chunks: bind pads nothing
        assert padded_layout(4 * spec["block_rows"] * n_dev, n_dev)[0] == 4 * spec["block_rows"] * n_dev


def test_labels_are_ascending_id_lists_with_their_pads(problem):
    for data in (problem.train, problem.test):
        lists = np.asarray(data.labels)
        assert lists.dtype == np.int32 and lists.shape == (len(data), 8) and data.n_labels == 1000
        assert lists.min() == -1 and lists.max() < 1000
        held = lists >= 0
        assert np.all(held[:, :-1] >= held[:, 1:])  # the pads come last
        both = held[:, :-1] & held[:, 1:]
        assert np.all((lists[:, 1:] > lists[:, :-1])[both])  # ascending, no id twice
    # a function of the seed
    again = amazoncat_like.generate(_config()["data"], 7, jax.devices()[:1], rehearse=True)
    other = amazoncat_like.generate(_config()["data"], 8, jax.devices()[:1], rehearse=True)
    np.testing.assert_array_equal(np.asarray(again.train.labels), np.asarray(problem.train.labels))
    assert not np.array_equal(np.asarray(other.train.labels), np.asarray(problem.train.labels))


def test_the_batchs_realised_positives_a_row_are_the_stated_share(problem):
    lists = np.concatenate([np.asarray(problem.train.labels), np.asarray(problem.test.labels)])
    mean = (lists >= 0).sum() / len(lists)
    assert abs(mean / 0.378 - 1.0) < 0.05, mean
    assert 0.6 < ((lists >= 0).sum(axis=1) == 0).mean() < 0.8  # most rows have none
    # frequent labels are the low ids: the batch is held in descending prior
    counts = np.bincount(lists[lists >= 0], minlength=1000)
    assert counts[:10].sum() > counts[-500:].sum()
