"""Device-mesh helpers: worker axis, dataset sharding, padding.

The reference's cluster topology — N worker processes each owning a
contiguous sample shard (SplitStrategy.scala:13-14) — maps onto a 1-D
``jax.sharding.Mesh`` with a ``workers`` axis: worker i == mesh position i,
its shard == the i-th slice of the batch-dimension-sharded resident
dataset.  Collectives over this axis (psum in parallel/sync.py) replace the
reference's gRPC star topology (Master.scala:179-198).  Multi-host runs use
the same axis over a global mesh (parallel/multihost.py); inside a slice
the collectives ride ICI, across slices DCN.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_sgd_tpu.data.rcv1 import Dataset
from distributed_sgd_tpu.utils import measure, metrics

WORKER_AXIS = "workers"


shard_map = jax.shard_map


def pcast_varying(x, axes: Tuple[str, ...]):
    """Cast a replicated value to varying over `axes`: shard_map's
    varying-mesh-axes typing requires it before per-device control flow."""
    return jax.lax.pcast(x, axes, to="varying")


def gather_replicated(x, axis: str):
    """Every device's `x` stacked along a new leading axis, typed as the
    same on every device (an all-gather; `jax.lax.all_gather` types its
    result as varying, and what is scattered into replicated weights has
    to be known replicated).  jax 0.9 keeps this form under `_src`."""
    from jax._src.lax.parallel import all_gather_invariant

    return all_gather_invariant(x, axis)


def make_mesh(n_workers: Optional[int] = None, devices=None) -> Mesh:
    """A 1-D mesh of `n_workers` devices along the `workers` axis."""
    devices = list(devices if devices is not None else jax.devices())
    if n_workers is None:
        n_workers = len(devices)
    if n_workers > len(devices):
        raise ValueError(f"n_workers={n_workers} > available devices {len(devices)}")
    return Mesh(np.asarray(devices[:n_workers]), (WORKER_AXIS,))


def local_device_groups(devices, n_workers: int, host_devices: int):
    """Deterministic contiguous device groups for hierarchical in-process
    clusters (core/cluster.py DevCluster, benches/bench_hier.py, the
    MULTICHIP dryrun): worker i gets devices [i*D, (i+1)*D), each group
    backing one WorkerNode's in-host mesh (parallel/hier.py).  Raises
    when the available devices cannot host the topology."""
    devices = list(devices)
    need = n_workers * host_devices
    if len(devices) < need:
        raise ValueError(
            f"{n_workers} workers x {host_devices} devices need {need} "
            f"devices, found {len(devices)}")
    return [devices[i * host_devices:(i + 1) * host_devices]
            for i in range(n_workers)]


def pad_to_multiple(data: Dataset, k: int) -> Dataset:
    """Pad with inert rows (all-zero features, label 0) so len % k == 0.

    Label 0 doubles as the validity mask: real labels are +/-1 (or nonzero
    float targets), so evaluation masks on `labels != 0`.
    """
    n = len(data)
    rem = (-n) % k
    if rem == 0:
        return data
    pad_idx = np.zeros((rem, data.indices.shape[1]), dtype=data.indices.dtype)
    pad_val = np.zeros((rem, data.values.shape[1]), dtype=data.values.dtype)
    pad_y = np.zeros((rem,), dtype=data.labels.dtype)
    return Dataset(
        indices=np.concatenate([data.indices, pad_idx]),
        values=np.concatenate([data.values, pad_val]),
        labels=np.concatenate([data.labels, pad_y]),
        n_features=data.n_features,
    )


def shard_dataset(data: Dataset, mesh: Mesh) -> Tuple[jax.Array, jax.Array, jax.Array, int]:
    """Place the packed dataset on the mesh, batch dim sharded over workers.

    Returns (indices, values, labels) as device arrays plus the true
    (pre-padding) sample count.  Worker i's shard is the i-th contiguous
    chunk — the same assignment as the reference's vanilla split.
    """
    n_true = len(data)
    k = mesh.shape[WORKER_AXIS]
    data = pad_to_multiple(data, k)
    sharding = NamedSharding(mesh, P(WORKER_AXIS))
    idx = jax.device_put(data.indices, sharding)
    val = jax.device_put(data.values, sharding)
    y = jax.device_put(data.labels, sharding)
    return idx, val, y, n_true


# Where resident rows are placed (put_rows).  A TPU stores a [rows, width]
# array whose width is not whole 128-lane tiles with the ROWS as the minor
# dimension (the layout that pads least: width rounded up to 8 sublanes),
# while every step gathers whole rows and reads them row-major; the
# compiler bridges the two with a copy of the WHOLE resident array at the
# entry of every program run (PERF.md section 6, PR 25: 12.6 ms of a
# 42.5 ms epoch program at f32[491520, 2000]).  An array of whole lanes is
# stored row-major, so rows are zero-padded to whole lanes on a TPU when
# that is nearly free: when the lane padding (width rounded up to 128)
# costs at most this much more than the default's sublane padding.  2,000
# wide: 2048 / 2000 = 1.024, padded; 76 wide: 128 / 80 = 1.6, left as it is
# (+60 % of the rows' HBM to save a copy that long epoch programs
# amortise).  Zero-width and 1-D arrays have no rows to gather; whole-lane
# widths and the CPU are row-major already.  The padding is the array's
# SHAPE and not a jax.experimental.layout.Format on it: an executable
# compiled for a non-default parameter layout comes back from the
# persistent compile cache expecting the default one (jax 0.9.0 / libtpu
# 0.0.34, PERF.md section 6).  The first lane of the padding holds the
# row's label where `label_slot` says so (lane 2,000 of 2,048): the gather
# that brings the row brings it.
ROW_MAJOR_MAX_PADDING = 1.125
_LANES, _SUBLANES = 128, 8
_PAD_CHUNK = 4096  # rows re-laid-out per step of the one-off padding program


def lane_width(shape: Tuple[int, ...], platform: str) -> Optional[int]:
    """The width `put_rows` pads an array of `shape` to: whole lanes where
    the rule above says it pays, None (as it comes) everywhere else."""
    if platform != "tpu" or len(shape) != 2 or shape[1] % _LANES == 0:
        return None
    lanes = -(-shape[1] // _LANES) * _LANES
    sublanes = -(-shape[1] // _SUBLANES) * _SUBLANES
    return lanes if lanes <= ROW_MAJOR_MAX_PADDING * sublanes else None


def _fill_by_chunks(rows: int, width: int, dtype, piece_at) -> jax.Array:
    """Zeros [rows, width] with `piece_at(start, count)` written over rows
    [start, start + count), a chunk of rows at a time so that a re-layout
    needs no second copy of the shard."""
    chunk = math.gcd(rows, _PAD_CHUNK)

    def body(out, t):
        return jax.lax.dynamic_update_slice(
            out, piece_at(t * chunk, chunk), (t * chunk, 0)), ()

    zeros = pcast_varying(jnp.zeros((rows, width), dtype), (WORKER_AXIS,))
    out, _ = jax.lax.scan(body, zeros, jnp.arange(rows // chunk))
    return out


def _pad_lanes(shard: jax.Array, *label: jax.Array, width: int) -> jax.Array:
    """One device's rows, zero-padded to `width`; with `label` ([rows], the
    rows' labels) each row's label in the first word past its values."""
    def piece_at(start, count):
        rows = jax.lax.dynamic_slice_in_dim(shard, start, count, 0)
        if not label:
            return rows
        y = jax.lax.dynamic_slice_in_dim(label[0], start, count, 0)
        return jnp.concatenate([rows, y.astype(shard.dtype)[:, None]], axis=1)

    return _fill_by_chunks(shard.shape[0], width, shard.dtype, piece_at)


def put_rows(arr, sharding: NamedSharding, width: Optional[int] = None,
             label=None) -> jax.Array:
    """Place one resident array, rows sharded over the workers, so that it
    is stored in the layout the step gathers from: as it comes, or (where
    `lane_width` says so) zero-padded to whole lanes, which the backend
    stores row-major.  Readers take the true width back off (BoundSync.rows
    / .chunk): the padding is never read.  The padding runs once, on the
    devices, from the default placement.  `width`: the caller's own padded
    width on every platform (the labels of a model with an output axis,
    which its readers WANT lane for lane beside the margins).  `label`: the
    rows' labels [rows], stored as the array's dtype in word `arr.shape[1]`
    of each row (`label_slot`): the first lane of the padding, or one more
    column of rows that stay rows-minor."""
    if width is None:
        width = lane_width(arr.shape, next(iter(sharding.device_set)).platform)
    elif width == arr.shape[1]:
        width = None
    name = "default" if width is None else "row_major"
    if label is not None and width is None:
        width = arr.shape[1] + 1  # rows-minor as before: no lane is padded
    with measure.span("sync.bind.place", layout=name, bytes=arr.nbytes):
        placed = jax.device_put(arr, sharding)
        if width is not None:
            args = (placed,) if label is None else (placed, jax.device_put(label, sharding))
            placed = jax.jit(shard_map(
                functools.partial(_pad_lanes, width=width), mesh=sharding.mesh,
                in_specs=(sharding.spec,) * len(args), out_specs=sharding.spec))(*args)
    metrics.counter(f"bind.rows.{name}").increment()
    return placed


# Narrow sparse rows (put_packed).  Rows too narrow for the rule above stay
# rows-minor, and a step then gathers its batch out of that layout: 43 us
# for 400 rows of 39 entries at criteo-logistic's shape, where a gather of
# whole row-major rows of the same bytes takes a tenth (PERF.md section 6,
# PR 26).  Where a row's indices AND values fit one 128-lane row of 32-bit
# words, bind() stores them side by side in one such row (lanes [0, P) the
# indices, [P, 2P) the values' bits), which the backend stores row-major:
# one gather a step instead of two, out of a layout that needs no copy.
# It costs HBM (512 B a row where 39-wide rows take 320, 11-wide rows 128),
# so it is done only while that is at most this factor: from 9 entries a
# row (16 sublanes) up to 64.  The narrowest rows measured are 11 wide
# (kdd2012-logistic's 6,488,064 train rows, the whole step of
# `BoundSync.epoch` over 2,000 steps, benches/sparse_update_sweep.py,
# PERF.md section 6, PR 30): one packed row a row 428.6 us a step, two
# rows-minor arrays 444.0.  The packed draw wins by 15.4 us at 4 x the
# bytes; rows of 8 entries and fewer (8 x) were not measured and stay two
# arrays.  Lane 2P, the first after the values' bits, holds the row's label
# where `label_slot` says so (every packed width but 64).
PACKED_MAX_PADDING = 4.0


def packed_width(width: int, platform: str) -> Optional[int]:
    """The lane count `put_packed` stores a row of `width` index / value
    pairs in, or None where rows stay two arrays."""
    if platform != "tpu" or width == 0 or 2 * width > _LANES:
        return None
    stored = 2 * (-(-width // _SUBLANES) * _SUBLANES)
    return _LANES if _LANES <= PACKED_MAX_PADDING * stored else None


# Where a row's label lies (label_slot).  A step draws its rows AND their
# labels, and `y[ids]` is a gather of single 4-byte words, which the chip
# walks one after the other (12.6 ns a word, ops/gather.py): 400 labels cost
# 5.8 us where 400 rows of 76 words cost 3.8 (`rcv1-sync-1chip`), half of
# `kdd2012-sync-1chip`'s draw (ledger, PR 32).  A row gather costs by the
# row, not by the lanes used, and every placement above leaves a 32-bit
# word of the stored row unused; a label written there arrives with the row
# and the step gathers no label at all.  It rides as float32 (the values'
# dtype; every `grad_coeff` casts its labels to float32 first, so the step
# computes the same).  What has no such word (64-wide packed rows, whole
# lanes, whole sublane groups), a label that is itself a row (an output
# axis) and every platform but the TPU (no padding, no sublane groups: a
# column more would be a copy more) keep the label the array it is.


def label_slot(width: int, lanes: Optional[int], outputs: int, platform: str) -> Optional[int]:
    """The word of a stored row in which `SyncEngine.bind` writes the row's
    label, for rows of `width` values stored in `lanes` packed lanes
    (`packed_width`'s answer; None: indices and values are two arrays) under
    a model of `outputs` outputs: a lane of the packed row, a column of the
    values array, or None where the label stays an array of its own."""
    if platform != "tpu" or outputs > 1 or width == 0:
        return None
    if lanes is not None:  # packed: the lane after the values' bits
        return 2 * width if 2 * width < lanes else None
    if lane_width((1, width), platform) is not None:  # the padding's first lane
        return width
    # rows-minor, `width` rounded up to whole sublane groups: one more
    # column where the last group has room (76 -> 77 of 80)
    return width if width % _SUBLANES else None


def _pack_lanes(idx: jax.Array, val: jax.Array, *label: jax.Array, lanes: int) -> jax.Array:
    """One device's rows as int32 [rows, lanes]: indices, then the values'
    bits, then (with `label`, the rows' labels [rows]) the label's bits as
    float32, then zeros."""
    def bits(x, start, count):
        return jax.lax.bitcast_convert_type(
            jax.lax.dynamic_slice_in_dim(x, start, count, 0).astype(jnp.float32), jnp.int32)

    def piece_at(start, count):
        return jnp.concatenate(
            [jax.lax.dynamic_slice_in_dim(idx, start, count, 0).astype(jnp.int32),
             bits(val, start, count)] + [bits(y, start, count)[:, None] for y in label], axis=1)

    return _fill_by_chunks(idx.shape[0], lanes, jnp.int32, piece_at)


def unpack_rows(packed: jax.Array, width: int):
    """(indices int32[..., width], values f32[..., width]) of packed rows."""
    return packed[..., :width], jax.lax.bitcast_convert_type(
        packed[..., width:2 * width], jnp.float32)


def put_packed(indices, values, lanes: int, sharding: NamedSharding, label=None) -> jax.Array:
    """Place a split's indices and values as ONE resident array of `lanes`
    32-bit lanes a row (`packed_width`), rows sharded over the workers.
    The packing runs once, on the devices, from the default placement.
    `label`: the rows' labels [rows], stored in lane `2 * width`
    (`label_slot`) as float32's bits."""
    args = (indices, values) if label is None else (indices, values, label)
    with measure.span("sync.bind.place", layout="packed",
                      bytes=indices.shape[0] * lanes * 4):
        packed = jax.jit(shard_map(
            functools.partial(_pack_lanes, lanes=lanes), mesh=sharding.mesh,
            in_specs=(sharding.spec,) * len(args), out_specs=sharding.spec))(
                *(jax.device_put(a, sharding) for a in args))
    metrics.counter("bind.rows.packed").increment()
    return packed


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
