"""Cold-start-to-first-epoch at reference scale (BASELINE.md section).

Measures every stage between "files on disk" and "first compiled training
epoch done" on the full 804,414-row corpus (data/corpus.py, reference text
format): native parse, python-fallback parse, CSR->padded pack, label
join, host->device transfer, and first-epoch compile+run.  The reference's
only gate on this path is parse < 40 s (DatasetTests.scala:11-23) with JVM
parallel collections; both parsers here are held to stopwatch numbers.

Usage: python benches/data_pipeline.py [--skip-python] [--folder DIR]
Prints one JSON line on stdout; human-readable stages go to stderr.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_sgd_tpu.data import _native
from distributed_sgd_tpu.data.corpus import write_rcv1_corpus
from distributed_sgd_tpu.data.rcv1 import (
    N_FEATURES,
    Dataset,
    dim_sparsity,
    merge_parts,
    pack_csr,
    parse_svm_file_py,
    read_labels,
    train_test_split,
)

BATCH = 100
N_WORKERS = 3
LR = 0.5
LAM = 1e-5


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def timed(label: str, fn):
    t0 = time.perf_counter()
    out = fn()
    dt = time.perf_counter() - t0
    log(f"{label}: {dt:.2f}s")
    return out, dt


def main() -> None:
    skip_python = "--skip-python" in sys.argv
    folder = "/tmp/rcv1_scale_bench"
    if "--folder" in sys.argv:
        folder = sys.argv[sys.argv.index("--folder") + 1]

    files = ["lyrl2004_vectors_train.dat"] + [
        f"lyrl2004_vectors_test_pt{d}.dat" for d in range(4)
    ]
    if not all(os.path.exists(os.path.join(folder, f)) for f in files):
        meta, write_s = timed("corpus write (setup, not cold start)",
                              lambda: write_rcv1_corpus(folder))
        log(f"  {meta['bytes']/1e6:.0f} MB, nnz/row={meta['nnz_per_row']:.1f}")
    total_bytes = sum(os.path.getsize(os.path.join(folder, f)) for f in files)

    assert _native.load() is not None, "native parser failed to build"
    paths = [os.path.join(folder, f) for f in files]

    parts, native_parse_s = timed(
        "native parse (5 files)", lambda: [_native.parse_svm_file(p) for p in paths]
    )
    n_rows = sum(len(p[0]) for p in parts)
    nnz = sum(len(p[2]) for p in parts)
    log(f"  {n_rows} rows, {nnz/1e6:.1f}M nnz, "
        f"{total_bytes/1e6/native_parse_s:.0f} MB/s")

    py_parse_s = None
    if not skip_python:
        _, py_parse_s = timed(
            "python-fallback parse (5 files)",
            lambda: [parse_svm_file_py(p) for p in paths],
        )

    def _pack():
        doc_ids, row_ptr, col_idx, values = merge_parts(parts)
        idx, val = pack_csr(row_ptr, col_idx, values)
        return doc_ids, idx, val

    (doc_ids, idx, val), pack_s = timed("pack CSR -> padded [N, P]", _pack)

    def _labels():
        lm = read_labels(os.path.join(folder, "rcv1-v2.topics.qrels"))
        return np.asarray([lm[int(d)] for d in doc_ids], dtype=np.int32)

    y, labels_s = timed("label read + join", _labels)

    ds = Dataset(indices=idx, values=val, labels=y, n_features=N_FEATURES)
    train, _test = train_test_split(ds)
    dsp, _ = timed("dim sparsity", lambda: dim_sparsity(train))

    import jax
    import jax.numpy as jnp

    from distributed_sgd_tpu.models.linear import SparseSVM
    from distributed_sgd_tpu.parallel.mesh import make_mesh
    from distributed_sgd_tpu.parallel.sync import SyncEngine

    log(f"device: {jax.devices()[0]}")
    model = SparseSVM(lam=LAM, n_features=N_FEATURES, dim_sparsity=jnp.asarray(dsp))
    engine = SyncEngine(
        model, make_mesh(1), batch_size=BATCH, learning_rate=LR,
        virtual_workers=N_WORKERS,
    )
    # bind() device_puts the packed train arrays; time it as the transfer
    bound, device_put_s = timed("bind + host->device transfer", lambda: engine.bind(train))

    w0 = jnp.zeros((N_FEATURES,), dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    _, first_epoch_s = timed(
        "first compiled epoch (compile + run)",
        lambda: np.asarray(bound.multi_epoch(w0, key, 1)),
    )

    cold = native_parse_s + pack_s + labels_s + device_put_s + first_epoch_s
    log(f"cold start (native parse -> first epoch done): {cold:.2f}s")

    print(json.dumps({
        "metric": "cold_start_to_first_epoch_seconds",
        "value": round(cold, 2),
        "unit": "s",
        "n_rows": n_rows,
        "corpus_mb": round(total_bytes / 1e6),
        "native_parse_s": round(native_parse_s, 2),
        "python_parse_s": round(py_parse_s, 2) if py_parse_s else None,
        "pack_s": round(pack_s, 2),
        "labels_s": round(labels_s, 2),
        "bind_device_put_s": round(device_put_s, 2),
        "first_epoch_s": round(first_epoch_s, 2),
        "reference_parse_gate_s": 40.0,
    }))


if __name__ == "__main__":
    from distributed_sgd_tpu import compile_cache

    compile_cache.place()
    main()
