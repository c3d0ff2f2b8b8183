"""Milliseconds per epoch in which nothing ran on the worst device outside
the epoch program and outside every `trainer.evaluate` span: the rest of
the fit loop (`trainer.bookkeeping`, `trainer.criterion`, `ckpt.save`, the
dispatch of the next epoch program inside `trainer.epoch`, and what no span
covers).  `eval_idle_ms + loop_idle_ms` is `boundary_idle_ms`; the
`program_spans:` line prints the split by span."""

from benchmark import program_spans


def read(run):
    idle = program_spans.part(run, "idle")
    if not idle or not idle["evaluate_spans"]:
        return None
    return idle["total_ms"] - idle["ms_per_epoch"]["trainer.evaluate"]
