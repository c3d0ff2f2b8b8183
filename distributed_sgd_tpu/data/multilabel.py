"""Multi-label rows whose labels are kept as ID LISTS.

A collection of extreme multi-label classification (the Extreme
Classification Repository's sets, DiSMEC's benchmarks) has thousands of
labels and a handful of positives a row, so a row's labels are stored as
the ids of its positives, `int32[N, Lw]` in ascending order (`LIST_PAD`
fills the unused slots), and not as one byte a (row, label) pair: 32 B a
row at Lw = 8 where a dense `[N, 1000]` takes 1 KB.  A one-vs-rest fit
holds a RANGE of the labels (DiSMEC's batch of 1,000 a node); ids are
relative to the range's first label, and `Dataset.n_labels` says how many
outputs the lists index.  The model expands a drawn batch's lists to the
rows of +1 / -1 its losses take (`models/linear.expand_labels`).

The text format is the repository's: a header `N D L`, then a line a row,
`l1,l2,... f:v f:v ...` (0-based ids; a row without labels starts with a
space).
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from distributed_sgd_tpu.data.rcv1 import LIST_PAD, Dataset, pack_csr


def to_lists(y: np.ndarray, width: Optional[int] = None) -> Tuple[np.ndarray, int]:
    """(int32[N, width] lists, rows cut) of dense labels `y[N, C]` (> 0 is
    positive): ascending ids, LIST_PAD after them.  `width` None: the
    longest row's.  A row with more positives than `width` keeps its lowest
    ids; the second value counts such rows."""
    positive = np.asarray(y) > 0
    counts = positive.sum(axis=1)
    width = int(width if width is not None else max(int(counts.max(initial=0)), 1))
    lists = np.full((len(positive), width), LIST_PAD, np.int32)
    rows, ids = np.nonzero(positive)  # row-major: a row's ids ascend
    slot = np.arange(len(rows)) - np.repeat(np.cumsum(counts) - counts, counts)
    keep = slot < width
    lists[rows[keep], slot[keep]] = ids[keep]
    return lists, int((counts > width).sum())


def read_multilabel(path: str, label_range: Optional[Tuple[int, int]] = None,
                    list_width: Optional[int] = None,
                    pad_width: Optional[int] = None) -> Dataset:
    """The file at `path` as a `Dataset` with label lists: the labels inside
    `label_range` = [first, end) (None: all L of the header), renumbered
    from 0, `list_width` slots a row (None: the longest row's; a longer row
    keeps its lowest ids), the features packed as `pack_csr` packs them."""
    with open(path, "r") as f:
        n_rows, n_features, n_labels = (int(t) for t in f.readline().split()[:3])
        first, end = label_range if label_range is not None else (0, n_labels)
        if not 0 <= first < end <= n_labels:
            raise ValueError(f"label range [{first}, {end}) is not inside [0, {n_labels})")
        kept, row_ptr, cols, vals = [], [0], [], []
        for line in f:
            if not line.strip():
                continue
            head, _, rest = line.partition(" ")
            ids = sorted(int(t) - first for t in head.split(",") if t)
            kept.append([i for i in ids if 0 <= i < end - first])
            for tok in rest.split():
                col, _, val = tok.partition(":")
                cols.append(int(col))
                vals.append(float(val))
            row_ptr.append(len(cols))
    if len(kept) != n_rows:
        raise ValueError(f"{path}: the header says {n_rows} rows, the file holds {len(kept)}")
    if cols and not 0 <= min(cols) <= max(cols) < n_features:
        raise ValueError(f"{path}: a feature id outside [0, {n_features})")
    width = int(list_width or max(max(map(len, kept), default=0), 1))
    lists = np.full((n_rows, width), LIST_PAD, np.int32)
    for n, ids in enumerate(kept):
        lists[n, :min(len(ids), width)] = ids[:width]
    idx, val = pack_csr(np.asarray(row_ptr, np.int64), np.asarray(cols, np.int32),
                        np.asarray(vals, np.float32), pad_width=pad_width)
    return Dataset(indices=idx, values=val, labels=lists, n_features=n_features,
                   n_labels=end - first)
