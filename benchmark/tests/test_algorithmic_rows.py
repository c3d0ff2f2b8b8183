"""The count behind `row_step_roofline`: arithmetic on shapes and on the
generator's own law, nothing imported from the program."""

import os

import numpy as np

from benchmark import algorithmic_rows, peaks
from benchmark.gen import rcv1_like
from benchmark.harness import ROOT


def test_it_imports_nothing_from_the_program():
    with open(os.path.join(ROOT, "benchmark", "algorithmic_rows.py")) as f:
        text = f.read()
    assert "distributed_sgd_tpu" not in text.split('"""')[2]


def test_the_law_is_the_generators():
    np.testing.assert_array_equal(algorithmic_rows.rank_prob(47236), rcv1_like.rank_prob(47236))
    np.testing.assert_allclose(algorithmic_rows.rank_prob(1000).sum(), 1.0, rtol=1e-12)


def test_distinct_ids_of_a_step_against_a_draw():
    d, draws = 47236, 4 * 100 * 76
    expected = algorithmic_rows.expected_distinct(d, draws)
    assert 9_000 < expected < 9_500  # of 30,400 entries
    rng = np.random.default_rng(0)
    seen = [len(np.unique(rng.choice(d, size=draws, p=algorithmic_rows.rank_prob(d))))
            for _ in range(8)]
    assert abs(np.mean(seen) - expected) < 60  # a step's count spreads by ~50
    np.testing.assert_allclose(algorithmic_rows.expected_distinct(d, 1), 1.0, rtol=1e-12)
    assert algorithmic_rows.expected_distinct(50, 10**6) > 49.99


def test_the_step_of_the_cell():
    k, b, p, c, d = 4, 100, 76, 103, 47236
    assert algorithmic_rows.step_flops(b, k, p, c) == 4 * 30_400 * 103 == 12_524_800
    distinct = algorithmic_rows.expected_distinct(d, k * b * p)
    nbytes = algorithmic_rows.step_bytes(b, k, p, c, d, label_bytes=1)
    assert nbytes == 400 * (8 * 76 + 4 + 103) + 12.0 * 103 * distinct
    # no term in D beyond the law: ten times the features move 1.44 x the bytes
    assert algorithmic_rows.step_bytes(b, k, p, c, 10 * d) < 1.5 * nbytes
    row = peaks.PEAKS["TPU v5 lite"]
    least = algorithmic_rows.least_seconds(12_524_800, nbytes, row)
    assert least == nbytes / row["hbm_bps"] > 12_524_800 / row["bf16_flops"]  # bytes bind
    assert 13e-6 < least < 16e-6


def test_the_reader_returns_nothing_where_there_is_nothing_to_read():
    from types import SimpleNamespace

    from benchmark.layer_metrics import row_step_roofline

    ctx = SimpleNamespace(peaks=peaks.PEAKS["TPU v5 lite"])
    engine = {"batch_size": 100, "virtual_workers": 4, "row_width": 76, "n_features": 47236,
              "n_outputs": 103, "label_bytes": 1, "dense": False}
    trace = {"worst_device": "TPU:0",
             "devices": {"TPU:0": {"program": {"step": {"seconds": 435.3e-6, "steps": 465}}}}}
    run = SimpleNamespace(trace=trace, ctx=ctx, engine=engine)
    np.testing.assert_allclose(row_step_roofline.read(run), 3.2812, rtol=1e-3)
    # a program without the output axis says nothing of outputs; no trace; no step
    flat = {k: v for k, v in engine.items() if k not in ("n_outputs", "label_bytes")}
    assert row_step_roofline.read(SimpleNamespace(trace=trace, ctx=ctx, engine=flat)) is None
    assert row_step_roofline.read(SimpleNamespace(trace=None, ctx=ctx, engine=engine)) is None
    empty = {"worst_device": "TPU:0", "devices": {"TPU:0": {}}}
    assert row_step_roofline.read(SimpleNamespace(trace=empty, ctx=ctx, engine=engine)) is None
