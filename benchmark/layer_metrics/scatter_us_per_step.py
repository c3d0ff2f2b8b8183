"""Microseconds a step of the compiled epoch spends in the backward product
(the "scatter": `OneHotBatch.scatter_add` in every formulation, the
`X^T coeff` of `grad_dense`, `grad_sum`): self time under the scope
`dsgd.scatter` inside the epoch program per step, first device, plus
`dsgd.coeff` (the loss derivative that feeds it, a fusion of its own only
where the compiler leaves it one)."""

from benchmark import program_spans


def read(run):
    return program_spans.scope_us_per_step(run, ("dsgd.scatter", "dsgd.coeff"))
