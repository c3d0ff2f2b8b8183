"""`algorithmic.py` against counts worked by hand."""

from benchmark import algorithmic
from benchmark.peaks import PEAKS


def test_sparse_step_flagship_shape():
    # B=100, K=4, nnz=76, D=47,236
    # margins 2*4*100*76 = 60,800; scatter the same             -> 121,600
    # regularizer 3*D per worker = 3*47,236*4                    -> 566,832
    # update 2*D                                                 ->  94,472
    # rows 4*100*(76*8+4) = 244,800 bytes; w r+w and ds: 12*D    -> 566,832
    work = algorithmic.step_work(100, 4, 47236, 76, dense=False)
    assert work == {"flops": 121_600 + 566_832 + 94_472, "bytes": 244_800 + 566_832}


def test_dense_step_epsilon_shape():
    # B=100, K=4, D=2,000
    # margins + gradient 4*4*100*2000 = 3,200,000; l2 2*D*4 = 16,000; update 4,000
    # rows 4*100*(8,000+4) = 3,201,600 bytes; w read and written 16,000
    work = algorithmic.step_work(100, 4, 2000, 2000, dense=True)
    assert work == {"flops": 3_220_000, "bytes": 3_217_600}


def test_both_shapes_are_bound_by_hbm_on_v5e():
    peaks = PEAKS["TPU v5 lite"]
    for work in (algorithmic.step_work(100, 4, 47236, 76, False),
                 algorithmic.step_work(100, 4, 2000, 2000, True)):
        least = algorithmic.least_step_seconds(work, peaks)
        assert least["bound"] == "bytes"
        assert least["seconds"] == work["bytes"] / 819e9
    # 811,632 bytes at 819 GB/s: just under a microsecond a step
    least = algorithmic.least_step_seconds(
        algorithmic.step_work(100, 4, 47236, 76, False), peaks)
    assert 0.9e-6 < least["seconds"] < 1.0e-6


def test_flops_bound_when_bytes_are_few():
    least = algorithmic.least_step_seconds({"flops": 1e12, "bytes": 1.0},
                                           {"bf16_flops": 1e12, "hbm_bps": 1e9})
    assert least["bound"] == "flops" and least["seconds"] == 1.0
