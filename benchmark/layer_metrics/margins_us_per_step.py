"""Microseconds a step of the compiled epoch spends in the forward product
(the "gather": `OneHotBatch.margins`, `LinearModel.margins_dense`, the
scalar `margins`): self time under the scope `dsgd.margins` inside the
epoch program per step, first device, plus `dsgd.onehot`.  On the v5e
(PR 24) the compiler fuses the one-hot operands into the matmuls that
consume them, so `dsgd.onehot` holds only the index arithmetic
(`idx // 128`, `idx % 128`: under a microsecond a step); it is counted
here because both products read it and the forward one comes first."""

from benchmark import program_spans


def read(run):
    return program_spans.scope_us_per_step(run, ("dsgd.margins", "dsgd.onehot"))
