"""`rcv1_topics_like`: `rcv1_like`'s rows seed for seed, a row of labels a
row, the priors the configuration states, reproducible."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.gen import rcv1_like, rcv1_topics_like
from benchmark.harness import ROOT
from distributed_sgd_tpu.parallel.sync import padded_layout


def _config(name="rcv1-topics-hinge"):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_priors_are_the_configurations_and_sum_to_the_codes_a_row():
    spec = _config()["data"]
    p = rcv1_topics_like.priors(spec)
    assert p.shape == (103,) and spec["n_outputs"] == _config()["n_outputs"] == 103
    np.testing.assert_allclose(p[:4], [0.474, 0.297, 0.255, 0.149])
    np.testing.assert_allclose(p.sum(), 3.24, rtol=1e-9)
    tail = p[4:]
    assert np.all(np.diff(tail) < 0) and tail[-1] == 8e-6 < 1e-5 and 0.19 < tail[0] < 0.21
    np.testing.assert_allclose(tail[1:] / tail[:-1], tail[1] / tail[0])  # geometric in rank
    # the planted share keeps the prior after the flips
    flip = rcv1_topics_like.flip_probability(p, 0.05)
    z = rcv1_topics_like.thresholds(p, 0.05)
    from statistics import NormalDist
    planted = np.asarray([1.0 - NormalDist().cdf(v) for v in z])
    np.testing.assert_allclose(planted * (1 - flip) + (1 - planted) * flip, p, rtol=1e-6)


def test_rows_are_rcv1_likes_and_labels_are_a_function_of_the_seed():
    topics, flat = _config()["data"], _config("rcv1-hinge")["data"]
    dev = jax.devices()[:1]
    a = rcv1_topics_like.generate(topics, 7, dev, rehearse=True)
    b = rcv1_topics_like.generate(topics, 7, dev, rehearse=True)
    c = rcv1_topics_like.generate(topics, 8, dev, rehearse=True)
    r = rcv1_like.generate(flat, 7, dev, rehearse=True)
    for split in ("train", "test"):
        for field in ("indices", "values"):
            np.testing.assert_array_equal(np.asarray(getattr(getattr(a, split), field)),
                                          np.asarray(getattr(getattr(r, split), field)))
        np.testing.assert_array_equal(np.asarray(getattr(a, split).labels),
                                      np.asarray(getattr(b, split).labels))
    y = np.asarray(a.train.labels)
    assert y.shape == (len(a.train), 103) and y.dtype == np.int8
    assert set(np.unique(y)) == {-1, 1}
    assert not np.array_equal(y, np.asarray(c.train.labels))
    assert a.dim_sparsity is None and a.n_features == 47236
    for n_dev in (1, 4):  # whole evaluation chunks: bind pads nothing
        p = rcv1_topics_like.generate(topics, 3, jax.devices()[:n_dev], rehearse=True)
        assert padded_layout(len(p.train), n_dev)[0] == len(p.train)
    assert topics["rows_per_chip"] == flat["rows_per_chip"] == 7208960


def test_realised_priors_lie_within_their_sampling_error():
    spec = _config()["data"]
    p = rcv1_topics_like.generate(spec, 11, jax.devices()[:1], rehearse=True)
    y = np.concatenate([np.asarray(p.train.labels), np.asarray(p.test.labels)])
    n = len(y)  # 40,960 rows
    share, want = (y > 0).mean(axis=0), rcv1_topics_like.priors(spec)
    # a topic's threshold is standardised over its sub-block, so its share
    # is binomial around the prior up to the margins' departure from a
    # normal tail: five standard errors and a twentieth of the prior
    sigma = np.sqrt(want * (1 - want) / n)
    assert np.all(np.abs(share - want) <= 5 * sigma + 0.05 * want + 2.0 / n)
    np.testing.assert_allclose(share.sum(), 3.24, rtol=0.02)


def test_a_program_without_the_output_axis_is_refused_at_once(monkeypatch, capsys):
    import pytest

    from distributed_sgd_tpu.models import linear

    monkeypatch.setattr(linear, "make_model", lambda name, lam, n_features: None)
    with pytest.raises(SystemExit) as e:
        rcv1_topics_like.generate(_config()["data"], 1, jax.devices()[:1], rehearse=True)
    assert e.value.code == 2 and "no output axis" in capsys.readouterr().err
