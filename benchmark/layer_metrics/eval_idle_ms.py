"""Milliseconds per epoch in which nothing ran on the worst device while
the fit loop was inside a `trainer.evaluate` span (`SyncTrainer.fit`: the
dispatch of the evaluation program, its two `float()` pulls, the eager
`lam*||w||^2`).  The gaps are `boundary_idle_ms`'s; this is the part of
them the evaluation's host code is answerable for."""

from benchmark import program_spans


def read(run):
    idle = program_spans.part(run, "idle")
    if not idle or not idle["evaluate_spans"]:
        return None
    return idle["ms_per_epoch"]["trainer.evaluate"]
