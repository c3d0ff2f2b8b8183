"""Milliseconds per epoch the first device spends in the evaluation
programs' forward product: self time of the operations that start inside a
`jit__eval_shard` event and whose innermost scope is `dsgd.margins` or
`dsgd.onehot` (the model's own names, nested under `dsgd.eval`; the same
two scopes `margins_us_per_step` reads inside the epoch program).  With
`eval_rows_ms`, `eval_other_ms` and `crumb_device_ms` it sums to
`eval_device_ms`.  None on a trace without `dsgd.eval_rows` (a commit
before PR 34)."""

from benchmark import boundary_spans


def read(run):
    return boundary_spans.metric(run, "eval_margins_ms")
