"""RCV1 dataset loading, packing, and statistics.

TPU-native re-design of the reference loader (utils/Dataset.scala:13-59)
and the dimSparsity pass (Main.scala:54-65):

- text parsing goes through the native C++ chunked parser
  (data/_native/parser.cpp) with a pure-numpy fallback, instead of Scala
  parallel collections over boxed maps;
- rows land in flat CSR, then are packed once into fixed-shape
  ``int32[N, P]`` / ``f32[N, P]`` padded arrays — the representation the
  TPU kernels (ops/sparse.py) consume; P defaults to the dataset's max nnz
  (lossless), or can be capped (rows are then truncated by largest |value|);
- feature ids are converted to 0-based at parse time.  The reference keeps
  the file's 1-based ids (Dataset.scala:24-33) while building dimSparsity
  0-based (Main.scala:63 ``buff(idx - 1)``) — we index consistently instead
  (see models/linear.py docstring for the parity note);
- label binarization reproduces the reference exactly, including the
  last-topic-wins quirk: ``readLabels(...).toMap`` (Dataset.scala:36-45,53)
  keeps only the LAST qrels line per doc id, so a doc in CCAT *and* any
  later-sorted topic (E*/G*/M*) binarizes to -1;
- the 80/20 split is contiguous ``splitAt(0.8 * n)`` (Main.scala:52).
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from distributed_sgd_tpu.data import _native

log = logging.getLogger("dsgd.data")

N_FEATURES = 47236  # Dataset.scala:16
LIST_PAD = -1  # an unused slot of a row's label list (Dataset.n_labels)
LIST_NO_ROW = -2  # every slot of a padding row's list: none of its outputs counts


@dataclass
class Dataset:
    """A packed sparse dataset: fixed-shape host arrays ready for device."""

    indices: np.ndarray  # int32[N, P], 0-based feature ids, 0-padded
    values: np.ndarray  # f32[N, P], 0.0-padded
    # int32[N], +/-1 (or float for regression); or [N, C] (int8), +/-1, one
    # column an output of a model with an output axis (every topic of the
    # qrels file: `load_rcv1(labels="topics")`); 0 is padding either way
    labels: np.ndarray
    n_features: int
    # > 0: `labels` is int32[N, Lw], the row's positive label ids among
    # `n_labels` outputs in ascending order (LIST_PAD fills a row's unused
    # slots, LIST_NO_ROW a whole padding row): what a row of thousands of
    # labels with a handful of positives is stored as (data/multilabel.py)
    n_labels: int = 0

    def __post_init__(self):
        if np.ndim(self.labels) not in (1, 2):
            raise ValueError(
                f"labels are [N] or [N, C], got shape {np.shape(self.labels)}")
        if self.n_labels and (self.n_labels < 2 or np.ndim(self.labels) != 2
                              or not np.issubdtype(self.labels.dtype, np.integer)):
            raise ValueError(
                f"label lists are integer ids [N, Lw] among n_labels >= 2 outputs, got "
                f"n_labels={self.n_labels}, labels {self.labels.dtype}{np.shape(self.labels)}")
        # a zero-width index array IS the dense-layout discriminator
        # (batches carry no n_features, so width 0 must imply dense
        # everywhere); sparse sets always pad to width >= 1 (pack_csr)
        if self.indices.shape[1] == 0 and self.values.shape[1] != self.n_features:
            raise ValueError(
                "zero-width indices mean dense layout: values must span all "
                f"{self.n_features} features, got width {self.values.shape[1]}"
            )

    def __len__(self) -> int:
        return self.values.shape[0]

    @property
    def pad_width(self) -> int:
        return self.values.shape[1]

    @property
    def is_dense(self) -> bool:
        """Dense layout: no index array (zero-width), values hold every
        feature.  Engines route these rows through plain-matmul kernels
        (models/linear.py dense fast path) instead of gather/scatter, and
        the int32 index array — which would double the footprint — is never
        materialized."""
        return self.indices.shape[1] == 0 and self.values.shape[1] == self.n_features

    @classmethod
    def dense(cls, values: np.ndarray, labels: np.ndarray) -> "Dataset":
        """Build a dense-layout dataset from values[N, D] + labels[N]."""
        values = np.ascontiguousarray(values, dtype=np.float32)
        return cls(
            indices=np.empty((values.shape[0], 0), dtype=np.int32),
            values=values,
            labels=np.asarray(labels),
            n_features=values.shape[1],
        )

    def slice(self, sel) -> "Dataset":
        return Dataset(self.indices[sel], self.values[sel], self.labels[sel], self.n_features,
                       self.n_labels)


def parse_svm_file_py(path: str, index_offset: int = -1):
    """Pure-python fallback parser -> (doc_ids, row_ptr, col_idx, values).

    Same format handling as the reference (Dataset.scala:19-34): first token
    is the doc id, remaining `f:v` tokens are features (the reference's
    `drop(2)` skips the empty token from the double space after the id;
    we split on arbitrary whitespace instead).  Streams line by line: a
    GIL-bound thread pool buys nothing for pure-python parsing, so the
    reference's chunk parallelism (.grouped(4096).par, Dataset.scala:21-22)
    lives in the native parser's threads and load_rcv1's per-file pool
    fan-out instead.
    """
    doc_ids: List[int] = []
    row_nnz: List[int] = []
    cols: List[int] = []
    vals: List[float] = []
    with open(path, "r") as f:
        for line in f:
            parts = line.split()
            if not parts:
                continue
            doc_ids.append(int(parts[0]))
            n = 0
            for tok in parts[1:]:
                if ":" not in tok:
                    continue
                k, v = tok.split(":", 1)
                cols.append(int(k) + index_offset)
                vals.append(float(v))
                n += 1
            row_nnz.append(n)
    row_ptr = np.zeros(len(doc_ids) + 1, dtype=np.int64)
    np.cumsum(row_nnz, out=row_ptr[1:])
    return (
        np.asarray(doc_ids, dtype=np.int32),
        row_ptr,
        np.asarray(cols, dtype=np.int32),
        np.asarray(vals, dtype=np.float32),
    )


def parse_svm_file(path: str, index_offset: int = -1, n_threads: int = 0):
    """Native parser with python fallback."""
    out = _native.parse_svm_file(path, n_threads=n_threads, index_offset=index_offset)
    if out is None:
        out = parse_svm_file_py(path, index_offset=index_offset)
    return out


def read_topics(path: str) -> Dict[int, List[str]]:
    """qrels 'topic docid 1' -> {docid: its topic codes, in file order}: the
    ONE parser of the file, which holds every topic code of every document
    (103 Topic categories in RCV1-v2, 3.24 a document on average)."""
    topics: Dict[int, List[str]] = {}
    with open(path, "r") as f:
        for line in f:
            parts = line.split()
            if len(parts) < 2:
                continue
            topics.setdefault(int(parts[1]), []).append(parts[0])
    return topics


def read_labels(path: str) -> Dict[int, int]:
    """qrels 'topic docid 1' -> {docid: +/-1}, CCAT -> +1, last line wins:
    the binary view of `read_topics`.

    Reproduces Dataset.scala:36-45,53 including the Iterator.toMap
    overwrite semantics (see module docstring).
    """
    return {doc: _ccat(codes) for doc, codes in read_topics(path).items()}


def _ccat(codes: List[str]) -> int:
    """A document's binary label: +1 where the LAST of its qrels lines says
    CCAT (`Iterator.toMap` keeps the last, Dataset.scala:53)."""
    return 1 if codes[-1] == "CCAT" else -1


def topic_matrix(topics: Dict[int, List[str]], doc_ids) -> Tuple[np.ndarray, List[str]]:
    """(int8[N, C] of +/-1, the C topic codes in sorted order): row n is
    document `doc_ids[n]`, +1 in the column of every code the file gives it
    (no last-line quirk here: every line counts)."""
    codes = sorted({c for doc in topics.values() for c in doc})
    column = {c: j for j, c in enumerate(codes)}
    y = np.full((len(doc_ids), len(codes)), -1, dtype=np.int8)
    for n, d in enumerate(doc_ids):
        y[n, [column[c] for c in topics[int(d)]]] = 1
    return y, codes


def pack_csr(
    row_ptr: np.ndarray,
    col_idx: np.ndarray,
    values: np.ndarray,
    pad_width: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """CSR -> padded [N, P] arrays.

    P defaults to max row nnz (lossless).  If a smaller P is forced, the
    affected rows keep their P largest-|value| features.  Uses the native
    row-loop pack (data/_native parser.cpp dsgd_pack_csr) when the library
    is available — the numpy scatter below was the slowest stage of
    full-scale loading — with identical output, truncation ties included.
    """
    nnz = np.diff(row_ptr).astype(np.int64)
    n = len(nnz)
    max_nnz = int(nnz.max()) if n else 0
    # width >= 1 always: a zero-width index array is the dense-layout
    # discriminator (Dataset.is_dense), so an all-empty-rows sparse set
    # pads to width 1 instead
    p = int(pad_width) if pad_width else max(max_nnz, 1)

    native = _native.pack_csr(row_ptr, col_idx, values, p)
    if native is not None:
        out_idx, out_val, truncated = native
    else:
        out_idx = np.zeros((n, p), dtype=np.int32)
        out_val = np.zeros((n, p), dtype=np.float32)
        pos_in_row = np.arange(len(col_idx), dtype=np.int64) - np.repeat(row_ptr[:-1], nnz)
        row_of = np.repeat(np.arange(n, dtype=np.int64), nnz)
        if max_nnz <= p:
            out_idx[row_of, pos_in_row] = col_idx
            out_val[row_of, pos_in_row] = values
            return out_idx, out_val
        over = np.nonzero(nnz > p)[0]
        keep = pos_in_row < p
        over_mask = np.isin(row_of, over)
        fast = keep & ~over_mask
        out_idx[row_of[fast], pos_in_row[fast]] = col_idx[fast]
        out_val[row_of[fast], pos_in_row[fast]] = values[fast]
        for r in over:  # rare rows: keep heaviest features, index-sorted
            s, e = row_ptr[r], row_ptr[r + 1]
            ci, cv = col_idx[s:e], values[s:e]
            sel = np.argsort(-np.abs(cv), kind="stable")[:p]  # ties: earliest wins
            sel.sort()
            out_idx[r, :p] = ci[sel]
            out_val[r, :p] = cv[sel]
        truncated = len(over)
    if truncated:
        log.warning("pad_width=%d truncated %d/%d rows (max nnz %d)", p, truncated, n, max_nnz)
    return out_idx, out_val


def merge_parts(parts) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Concatenate per-file (doc_ids, row_ptr, col_idx, values) CSR parts
    into one CSR with a rebuilt global row_ptr."""
    doc_ids = np.concatenate([p[0] for p in parts])
    col_idx = np.concatenate([p[2] for p in parts])
    values = np.concatenate([p[3] for p in parts])
    row_ptr = np.zeros(len(doc_ids) + 1, dtype=np.int64)
    np.cumsum(np.concatenate([np.diff(p[1]) for p in parts]), out=row_ptr[1:])
    return doc_ids, row_ptr, col_idx, values


def dim_sparsity(train: "Dataset") -> np.ndarray:
    """Inverse-document-frequency vector: 1/(count_i + 1) where feature i
    appears in the train split, else 0 (Main.scala:54-65)."""
    if train.is_dense:
        counts = (train.values != 0).sum(axis=0)
    else:
        idx = train.indices[train.values != 0]
        counts = np.bincount(idx.ravel(), minlength=train.n_features)
    out = np.zeros(train.n_features, dtype=np.float32)
    nz = counts > 0
    out[nz] = 1.0 / (counts[nz] + 1.0)
    return out


def train_test_split(data: "Dataset") -> Tuple["Dataset", "Dataset"]:
    """Contiguous 80/20 split (Main.scala:52)."""
    cut = int(len(data) * 0.8)
    return data.slice(slice(0, cut)), data.slice(slice(cut, None))


def load_rcv1(
    folder: str,
    full: bool = False,
    n_features: int = N_FEATURES,
    pad_width: Optional[int] = None,
    n_threads: int = 0,
    labels: str = "ccat",
) -> "Dataset":
    """Load RCV1 from `folder` (same file set as Dataset.scala:47-50).
    `labels`: 'ccat', the reference's one bit a document (`read_labels`),
    or 'topics', every topic code of the qrels file as [N, C] (`topic_matrix`:
    one output a code, for a model with `n_outputs` = C)."""
    if labels not in ("ccat", "topics"):
        raise ValueError(f"labels must be 'ccat' or 'topics', got {labels!r}")
    files = [os.path.join(folder, "lyrl2004_vectors_train.dat")]
    if full:
        files += [os.path.join(folder, f"lyrl2004_vectors_test_pt{d}.dat") for d in range(4)]
    topics = read_topics(os.path.join(folder, "rcv1-v2.topics.qrels"))

    # With auto threading (n_threads=0) and several files, fan out one parse
    # per file on the shared pool — the native parser releases the GIL
    # inside the ctypes call, so files parse concurrently (the reference's
    # .par chunk parallelism, one level up) — and split the core budget so
    # concurrent parses don't oversubscribe.  An EXPLICIT n_threads is a
    # per-parse budget: honor it with sequential parses.
    cores = os.cpu_count() or 1
    if n_threads == 0 and len(files) > 1 and cores >= 2 * len(files):
        from distributed_sgd_tpu.utils.pool import global_pool

        per_file = cores // len(files)
        parts = global_pool().map(
            lambda f: parse_svm_file(f, n_threads=per_file), files
        )
    else:
        parts = [parse_svm_file(f, n_threads=n_threads) for f in files]
    doc_ids, row_ptr, col_idx, values = merge_parts(parts)

    idx, val = pack_csr(row_ptr, col_idx, values, pad_width=pad_width)
    if labels == "topics":
        y, codes = topic_matrix(topics, doc_ids)
        log.info("labels: %d topic codes a row (%s ... %s)", len(codes), codes[0], codes[-1])
    else:  # CCAT against the rest, the last qrels line of a document deciding
        y = np.asarray([_ccat(topics[int(d)]) for d in doc_ids], dtype=np.int32)
    return Dataset(indices=idx, values=val, labels=y, n_features=n_features)
