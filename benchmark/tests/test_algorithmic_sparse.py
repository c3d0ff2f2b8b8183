"""The byte counts behind `gather_scatter_roofline` and
`sparse_step_roofline`: shapes only, the same work whatever implements it."""

import pytest

from benchmark import algorithmic, algorithmic_sparse, peaks


def test_entries_of_the_criteo_step():
    moved = algorithmic_sparse.gather_scatter_bytes(batch=100, workers_on_device=4, nnz=39)
    entries = 4 * 100 * 39
    assert moved == {"gather": 12 * entries, "scatter": 16 * entries}
    assert moved["gather"] + moved["scatter"] == 436_800


def test_the_whole_step_counts_rows_entries_and_w_once_each():
    n = algorithmic_sparse.step_bytes(batch=100, workers_on_device=4, n_features=1_000_000, nnz=39)
    rows = 4 * 100 * (8 * 39 + 4)
    assert n == rows + 436_800 + 8_000_000 == 8_563_200
    # independent of how many features there are, but for w's own pass
    small = algorithmic_sparse.step_bytes(100, 4, 47_236, 39)
    assert n - small == 8 * (1_000_000 - 47_236)


def test_no_dim_sparsity_read_unlike_the_accepted_sparse_count():
    """`algorithmic.step_work`'s sparse branch reads a 4D-byte regulariser
    vector that `l2` never reads: the reason the criteo cell has a count of
    its own."""
    accepted = algorithmic.step_work(100, 4, 1_000_000, 39, dense=False)["bytes"]
    entries = algorithmic_sparse.gather_scatter_bytes(100, 4, 39)
    ours = algorithmic_sparse.step_bytes(100, 4, 1_000_000, 39)
    assert accepted - (ours - entries["gather"] - entries["scatter"]) == 4 * 1_000_000


def test_least_times_on_a_v5e_and_shares_stay_under_100():
    row = peaks.peaks_for("TPU v5 lite")
    step = algorithmic_sparse.least_seconds(8_563_200, row)
    assert step == pytest.approx(8_563_200 / 819e9)  # 10.46 us
    entries = algorithmic_sparse.least_seconds(436_800, row)
    assert entries == pytest.approx(0.533e-6, rel=1e-2)
    # a step cannot be measured faster than its least time: at the 200-300 us
    # a v5e takes, both shares read a few percent
    assert 100 * step / 215e-6 < 6 and 100 * entries / 150e-6 < 1


class _Ctx:
    peaks = peaks.peaks_for("TPU v5 lite")


class _Run:
    ctx = _Ctx()
    engine = {"batch_size": 100, "virtual_workers": 4, "n_features": 1_000_000,
              "row_width": 39, "dense": False}
    trace = {"worst_device": "TPU:0",
             "devices": {"TPU:0": {"program": {"step": {"seconds": 200e-6, "steps": 10}}}}}
    trace_path = None


def test_the_readers_return_a_share_or_nothing_and_never_raise():
    from benchmark.layer_metrics import gather_scatter_roofline, sparse_step_roofline

    run = _Run()
    assert sparse_step_roofline.read(run) == pytest.approx(100 * (8_563_200 / 819e9) / 200e-6)
    assert gather_scatter_roofline.read(run) is None  # no trace file: no scopes to read

    class Dense(_Run):
        engine = dict(_Run.engine, dense=True, row_width=2000)

    class NoStep(_Run):
        trace = {"worst_device": "TPU:0", "devices": {"TPU:0": {}}}

    class Untraced(_Run):
        trace = None

    class Hogwild(_Run):
        engine = {"kernel": "scalar"}

    for other in (Dense(), NoStep(), Untraced(), Hogwild()):
        assert sparse_step_roofline.read(other) is None
        assert gather_scatter_roofline.read(other) is None
