"""The kernel plan of a sync binding (ops/kernels.py `plan`) for each
benchmark configuration at its cell's shapes, with the platform probe
(`mxu.blocked_pays_off`) steered to a TPU and to a CPU.  Each case is pinned
to the fields the cell's `train split:` record prints, and to the two
decisions the record does not print: the step's margin fetch and whether
weight rows are carried as tiles."""

import numpy as np
import pytest

from distributed_sgd_tpu.models.linear import make_model
from distributed_sgd_tpu.ops import kernels, mxu

# configuration: (loss, features, outputs, regulariser, lam, learning rate,
# stored entries a row (0: dense rows), (devices, workers a device), labels:
# id lists, or riding in a spare word of the stored row on a TPU)
SHAPES = {
    "rcv1-hinge": ("hinge", 47_236, 1, "dim_sparsity", 1e-05, 0.5, 76, (1, 4), "riding"),
    "rcv1-hinge-4chip": ("hinge", 47_236, 1, "dim_sparsity", 1e-05, 0.5, 76, (4, 1), "riding"),
    "epsilon-logistic": ("logistic", 2_000, 1, "l2", 2.0345052083333333e-06, 0.05, 0, (1, 4),
                         "riding"),
    "criteo-logistic": ("logistic", 1_000_000, 1, "l2", 9.247750946969697e-08, 0.1, 39,
                        (1, 4), "riding"),
    "kdd2012-logistic": ("logistic", 54_686_452, 1, "l2", 1.5412918244949496e-07, 0.1, 11,
                         (1, 4), "riding"),
    "rcv1-topics-hinge": ("hinge", 47_236, 103, "l2", 1.7339533025568182e-07, 0.25, 76,
                          (1, 4), "gathered"),
    "amazoncat13k-dismec": ("squared_hinge", 203_882, 1_000, "l2", 8.360980308219178e-07, 0.1,
                            72, (1, 4), "lists"),
}

# (configuration, platform): the record's fields, the step's margin fetch,
# rows carried as tiles
PLANS = {
    ("rcv1-hinge", "tpu"): (
        "kernel=mxu margins=merged scatter_shards=1 update=dense scatter=words outputs=1 "
        "labels=in_row eval_rows=4096 margin_fetch=gather optimizer=sgd", ("gather", 400), False),
    ("rcv1-hinge", "cpu"): (
        "kernel=mxu margins=merged scatter_shards=1 update=dense scatter=words outputs=1 "
        "labels=gathered eval_rows=4096 margin_fetch=gather optimizer=sgd", ("gather", 400), False),
    ("rcv1-hinge-4chip", "tpu"): (
        "kernel=mxu margins=per_worker scatter_shards=1 update=dense scatter=words outputs=1 "
        "labels=in_row eval_rows=4096 margin_fetch=gather optimizer=sgd", ("gather", 100), False),
    ("rcv1-hinge-4chip", "cpu"): (
        "kernel=mxu margins=per_worker scatter_shards=1 update=dense scatter=words outputs=1 "
        "labels=gathered eval_rows=4096 margin_fetch=gather optimizer=sgd", ("gather", 100), False),
    ("epsilon-logistic", "tpu"): (
        "kernel=dense margins=per_worker scatter_shards=1 update=dense scatter=words "
        "outputs=1 labels=in_row eval_rows=4096 margin_fetch=gather optimizer=sgd", ("gather", 400), False),
    ("epsilon-logistic", "cpu"): (
        "kernel=dense margins=per_worker scatter_shards=1 update=dense scatter=words "
        "outputs=1 labels=gathered eval_rows=4096 margin_fetch=gather optimizer=sgd", ("gather", 400),
        False),
    ("criteo-logistic", "tpu"): (
        "kernel=gather margins=merged scatter_shards=1 update=dense scatter=words outputs=1 "
        "labels=in_row eval_rows=4096 margin_fetch=gather optimizer=sgd", ("gather", 400), False),
    ("criteo-logistic", "cpu"): (
        "kernel=gather margins=merged scatter_shards=1 update=dense scatter=words outputs=1 "
        "labels=gathered eval_rows=4096 margin_fetch=gather optimizer=sgd", ("gather", 400), False),
    ("kdd2012-logistic", "tpu"): (
        "kernel=gather margins=merged scatter_shards=1 update=sparse scatter=rows outputs=1 "
        "labels=in_row eval_rows=4096 margin_fetch=gather optimizer=sgd", ("gather", 400), False),
    ("kdd2012-logistic", "cpu"): (
        "kernel=gather margins=merged scatter_shards=1 update=sparse scatter=words outputs=1 "
        "labels=gathered eval_rows=4096 margin_fetch=gather optimizer=sgd", ("gather", 400), False),
    ("rcv1-topics-hinge", "tpu"): (
        "kernel=gather margins=merged scatter_shards=1 update=sparse scatter=merge "
        "outputs=103 labels=gathered eval_rows=4096 margin_fetch=gather optimizer=sgd", ("gather", 400),
        False),
    ("rcv1-topics-hinge", "cpu"): (
        "kernel=gather margins=merged scatter_shards=1 update=sparse scatter=words "
        "outputs=103 labels=gathered eval_rows=4096 margin_fetch=gather optimizer=sgd", ("gather", 400),
        False),
    # a chunk's margins in pieces of 256 samples through the margin kernel on
    # the TPU, planned at bind (its rows never change), 512 a row gather
    # elsewhere; a step's 400 in pieces of 200, sorted and walked every step
    ("amazoncat13k-dismec", "tpu"): (
        "kernel=gather margins=merged scatter_shards=1 update=sparse scatter=runs "
        "outputs=1000 labels=lists eval_rows=256 margin_fetch=planned optimizer=sgd", ("distinct", 200),
        True),
    ("amazoncat13k-dismec", "cpu"): (
        "kernel=gather margins=merged scatter_shards=1 update=sparse scatter=words "
        "outputs=1000 labels=lists eval_rows=512 margin_fetch=gather optimizer=sgd", ("gather", 400), True),
}


@pytest.mark.parametrize("config,platform", sorted(PLANS))
def test_each_configuration_plans_what_its_cell_records(config, platform, monkeypatch):
    monkeypatch.setattr(mxu, "blocked_pays_off", lambda device=None: platform == "tpu")
    loss, features, outputs, regularizer, lam, lr, width, (devices, workers), labels = (
        SHAPES[config])
    model = make_model(loss, lam, features, regularizer=regularizer, n_outputs=outputs,
                       dim_sparsity=np.ones(features) if regularizer == "dim_sparsity" else None)
    plan = kernels.plan(
        model, learning_rate=lr, optimizer="sgd", row_width=width, virtual_workers=workers,
        batch_size=100, n_workers=devices, eval_chunk=4096, lists=labels == "lists",
        riding=labels == "riding" and platform == "tpu")
    record, step_fetch, tiles = PLANS[config, platform]
    assert plan.record() == record
    assert (plan.step_fetch, plan.tiles) == (step_fetch, tiles)
