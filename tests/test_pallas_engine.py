"""Engine-level check: the 'pallas' kernel backend produces the same
training trajectory as the 'mxu' backend (interpreter on the CPU mesh;
chip_smoke.py repeats the one-step check compiled on the chip)."""

import jax
import jax.numpy as jnp
import numpy as np

from distributed_sgd_tpu.data.synthetic import rcv1_like
from distributed_sgd_tpu.models.linear import SparseSVM
from distributed_sgd_tpu.parallel.mesh import make_mesh
from distributed_sgd_tpu.parallel.sync import SyncEngine


def test_pallas_engine_matches_mxu():
    d = 300
    data = rcv1_like(64, n_features=d, nnz=9, seed=0)
    ds = np.abs(np.random.default_rng(1).normal(size=d)).astype(np.float32) * 0.01
    model = SparseSVM(lam=1e-3, n_features=d, dim_sparsity=jnp.asarray(ds))
    mesh = make_mesh(2)
    w0 = jnp.asarray(np.random.default_rng(2).normal(size=d) * 0.05, dtype=jnp.float32)
    key = jax.random.PRNGKey(7)

    outs = {}
    for kernel in ("mxu", "pallas"):
        eng = SyncEngine(
            model, mesh, batch_size=4, learning_rate=0.3,
            kernel=kernel, virtual_workers=2,
        )
        bound = eng.bind(data)
        outs[kernel] = (
            np.asarray(bound.step(w0, key)),
            np.asarray(bound.epoch(w0, key)),
        )
    np.testing.assert_allclose(outs["pallas"][0], outs["mxu"][0], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(outs["pallas"][1], outs["mxu"][1], rtol=1e-3, atol=1e-5)
