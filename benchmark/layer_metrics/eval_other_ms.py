"""Milliseconds per epoch of the evaluation programs' busy time on the
first device under any scope but the margins' and `dsgd.eval_rows`, or
none: `dsgd.eval_reduce` (losses, hits, their sums), `dsgd.layout`,
`dsgd.allreduce`, bare `dsgd.eval`, and the loop control no operation
accounts for.  The `boundary_spans:` line prints it scope by scope."""

from benchmark import boundary_spans


def read(run):
    return boundary_spans.metric(run, "eval_other_ms")
