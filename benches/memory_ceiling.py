"""Measured per-chip corpus memory ceiling (VERDICT r3 item 8).

`SyncEngine.bind` materializes the full padded corpus host-side and
device-puts it once; the resident dataset then lives in HBM for the whole
fit.  This script measures, on the real chip: the HBM footprint of the
RCV1-scale corpus, the total/free HBM, and the implied max resident rows
at this row width — the number a user needs to decide when to switch to
the host-local loader path (parallel/multihost.py + per-host binds, the
pattern of tests/test_multihost_2proc.py) or a padded width cap
(load_rcv1(pad_width=...)).

Prints one JSON line; README/BASELINE record the numbers.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_ROWS = 804_414
N_FEATURES = 47_236
NNZ = 76


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> None:
    import jax
    import jax.numpy as jnp

    from distributed_sgd_tpu.data.synthetic import rcv1_like
    from distributed_sgd_tpu.models.linear import SparseSVM
    from distributed_sgd_tpu.parallel.mesh import make_mesh
    from distributed_sgd_tpu.parallel.sync import SyncEngine

    import time

    from distributed_sgd_tpu.parallel.sync import padded_layout

    dev = jax.devices()[0]
    data = rcv1_like(N_ROWS, n_features=N_FEATURES, nnz=NNZ, seed=0)
    p = data.pad_width
    host_bytes = data.indices.nbytes + data.values.nbytes + data.labels.nbytes
    log(f"host corpus: {host_bytes/1e6:.0f} MB (P={p})")

    model = SparseSVM(lam=1e-5, n_features=N_FEATURES, regularizer="l2")
    eng = SyncEngine(model, make_mesh(1), batch_size=100, learning_rate=0.5)
    t0 = time.perf_counter()
    bound = eng.bind(data)
    jax.block_until_ready(bound.data.values)
    bind_s = time.perf_counter() - t0

    # resident-dataset device bytes are deterministic from the padded
    # layout: int32[P] + f32[P] + int32 label per padded row
    total_padded, _ = padded_layout(N_ROWS, 1, 4096)
    bytes_per_row = 8 * p + 4
    corpus_dev = total_padded * bytes_per_row
    # the runtime's own limit when it reports one, else the published HBM
    # of this device_kind (an unlisted device is an error, not 16 GiB)
    stats = dev.memory_stats() or {}
    limit = int(stats.get("bytes_limit", 0))
    if not limit:
        from benches.device_peaks import peaks_for

        limit = peaks_for(dev)["hbm_bytes"]
    out = {
        "metric": "corpus_hbm_footprint",
        "pad_width": p,
        "host_corpus_mb": round(host_bytes / 1e6),
        "device_corpus_mb": round(corpus_dev / 1e6),
        "bytes_per_row": bytes_per_row,
        "bind_wall_s": round(bind_s, 2),
        "hbm_limit_mb": round(limit / 1e6),
        "hbm_limit_source": ("memory_stats" if stats.get("bytes_limit")
                             else "benches/device_peaks.py"),
        "device_kind": dev.device_kind,
        # ~1 GB headroom held back for weights (2 x 24 MB blocked copies),
        # the one-hot step working set, and XLA scratch
        "implied_max_rows_this_width": int((limit - 1e9) / bytes_per_row),
        "device": str(dev),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    from distributed_sgd_tpu import compile_cache

    compile_cache.place()
    main()
