"""Milliseconds per epoch in which nothing ran on the worst device while the
host was inside `trainer.evaluate.reg`: the eager `lam*||w||^2` (convert,
power, sum: three programs) and the pull of its scalar."""

from benchmark import boundary_spans


def read(run):
    return boundary_spans.metric(run, "eval_reg_idle_ms")
