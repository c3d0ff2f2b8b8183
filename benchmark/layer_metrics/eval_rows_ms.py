"""Milliseconds per epoch the first device spends fetching the evaluation's
chunks: self time under the scope `dsgd.eval_rows` inside `jit__eval_shard`
events (`BoundSync.chunk_rows`, the label slice and the mask: the slices of
the resident rows and whatever re-layout the compiler puts there, the
unpacking of packed rows)."""

from benchmark import boundary_spans


def read(run):
    return boundary_spans.metric(run, "eval_rows_ms")
