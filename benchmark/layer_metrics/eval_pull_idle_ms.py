"""Milliseconds per epoch in which nothing ran on the worst device while the
host was inside `trainer.evaluate.pull` (since PR 34: the `float()` pull of
the evaluation's second sum alone: an eager slice, a squeeze and a transfer
that the host starts once it is awake; the first pull rides behind the
evaluation program and is `.wait`'s).  The gaps are `boundary_idle_ms`'s;
with the idle inside `.dispatch`, `.wait` (both printed) and `.reg` it sums
to `eval_idle_ms`.  None on a trace without `trainer.evaluate.wait` spans
(a commit before PR 34, whose `.pull` held the wait, both pulls and the
regulariser)."""

from benchmark import boundary_spans


def read(run):
    return boundary_spans.metric(run, "eval_pull_idle_ms")
