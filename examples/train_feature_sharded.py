"""Tensor-parallel (dp x tp) training on a 2-D device mesh: weights
feature-sharded over the blocked rows, data row-sharded over workers.

Needs workers x shards devices — run on a pod slice, or locally on the
virtual CPU mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_feature_sharded.py [n_samples]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp  # noqa: E402

from distributed_sgd_tpu.core.early_stopping import no_improvement  # noqa: E402
from distributed_sgd_tpu.data.rcv1 import dim_sparsity, train_test_split  # noqa: E402
from distributed_sgd_tpu.data.synthetic import rcv1_like  # noqa: E402
from distributed_sgd_tpu.models.linear import make_model  # noqa: E402
from distributed_sgd_tpu.parallel.feature_sharded import (  # noqa: E402
    FeatureShardedEngine,
    make_mesh_2d,
)


def main(n: int = 4_000, max_epochs: int = 4, workers: int = 2,
         shards: int = 4) -> float:
    data = rcv1_like(n, n_features=2048, nnz=12, seed=0, idf_values=True)
    train, test = train_test_split(data)
    model = make_model(
        "hinge", 1e-5, data.n_features,
        dim_sparsity=jnp.asarray(dim_sparsity(train)),
    )
    # each device holds 1/shards of the blocked weight rows; margins are
    # TP partial sums over the 'features' axis, gradients DP-mean over
    # 'workers' — the same fit/evaluate contract as the 1-D SyncTrainer
    engine = FeatureShardedEngine(
        model, make_mesh_2d(workers, shards), batch_size=32, learning_rate=0.5
    )
    res = engine.fit(
        train, test, max_epochs,
        criterion=no_improvement(patience=3, min_delta=0.01),
    )
    print(f"dp={workers} tp={shards}: epochs={res.epochs_run} "
          f"test_loss={res.test_losses[-1]:.4f} "
          f"test_acc={res.test_accuracies[-1]:.4f}")
    return res.test_losses[-1]


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 4_000)
