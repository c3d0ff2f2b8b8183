from distributed_sgd_tpu.ops.sparse import (  # noqa: F401
    SparseBatch,
    matvec,
    pad_rows,
    scatter_add,
)

# Kernel families live in submodules (import explicitly; none are loaded
# eagerly so production imports stay lean):
# - ops.mxu           lane-blocked one-hot MXU kernels (default hot path)
# - ops.flat_sparse   flat CSR-style layout (SparseArrayVector parity)
# - ops.gradcheck     central-difference gradient checking (F parity)
