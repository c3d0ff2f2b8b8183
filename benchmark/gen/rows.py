"""What a generator hands the harness, and the layout rule both share.

A generator `benchmark/gen/<name>.py` exposes

    generate(spec, seed, devices, rehearse) -> Problem

and builds the rows ON THE DEVICES, in one jitted call from the seed: host
generation of the flagship corpus took 11.5 s for 804,414 rows (PERF.md,
PR 21) and every run of every later check would pay it.

Layout.  Each device generates `train_blocks` + `test_blocks` blocks of
`block_rows` rows; block `b`'s rows are a function of (seed, b) alone.
The train split is the concatenation of every device's train blocks, the
test split likewise, so `train ++ test` cut 80/20 by the program's
contiguous `train_test_split` gives back exactly (train, test) when
train_blocks : test_blocks = 4 : 1.  `block_rows` is a multiple of the
sync engine's evaluation chunk (4096), so `SyncEngine.bind` finds nothing
to pad and places the device-resident arrays without a trip through the
host.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import numpy as np

GEN_AXIS = "gen"
EVAL_CHUNK = 4096  # parallel/sync.py padded_layout default


@dataclass
class Problem:
    train: Any  # distributed_sgd_tpu.data.rcv1.Dataset over device arrays
    test: Any
    n_features: int
    dim_sparsity: Optional[np.ndarray]  # the regularizer's vector, or None
    rows_per_device: int


def layout(spec: dict, rehearse: bool) -> tuple:
    """(block_rows, train_blocks, test_blocks) of one device."""
    block_rows = int(spec["block_rows"])
    train_blocks, test_blocks = int(spec["train_blocks"]), int(spec["test_blocks"])
    if block_rows * (train_blocks + test_blocks) != int(spec["rows_per_chip"]):
        raise ValueError("rows_per_chip must equal block_rows * (train_blocks + test_blocks)")
    if train_blocks != 4 * test_blocks:
        raise ValueError("train_blocks : test_blocks must be 4 : 1 (the program's 80/20 split)")
    if block_rows % EVAL_CHUNK:
        raise ValueError(f"block_rows must be a multiple of {EVAL_CHUNK}")
    if rehearse:
        # the rehearsal keeps the block structure and shrinks the block
        block_rows = int(spec.get("rehearse_block_rows", EVAL_CHUNK))
    return block_rows, train_blocks, test_blocks


def device_splits(spec: dict, seed: int, devices, rehearse: bool,
                  block_of: Callable) -> tuple:
    """(train arrays, test arrays, rows per device), every array sharded by
    rows over `devices`, from one jitted call.

    `block_of(key, salt, block_rows)` returns the function that maps a
    global block id to that block's arrays.  `salt` is the seed as a traced
    value: a python int would be a constant of the program, and every seed
    would compile anew and miss the persistent cache.  Global block ids run
    over every device's train blocks first, then the test blocks, so a
    device's rows do not depend on how many devices there are."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    block_rows, train_blocks, test_blocks = layout(spec, rehearse)
    n_dev = len(devices)

    def per_device(key, salt):
        d = jax.lax.axis_index(GEN_AXIS)
        one = block_of(key, salt, block_rows)

        def split(first, count):
            stacked = jax.lax.map(one, first + jnp.arange(count))
            return tuple(a.reshape((-1,) + a.shape[2:]) for a in stacked)

        return (split(d * train_blocks, train_blocks),
                split(n_dev * train_blocks + d * test_blocks, test_blocks))

    train, test = jax.jit(jax.shard_map(
        per_device, mesh=Mesh(np.asarray(devices), (GEN_AXIS,)),
        in_specs=(P(), P()), out_specs=P(GEN_AXIS),
    ))(jax.random.PRNGKey(seed), jnp.uint32(seed & 0xFFFFFFFF))
    return train, test, block_rows * (train_blocks + test_blocks)
