"""Program runs per epoch on the first device: events of `XLA Modules` in
the traced window over the epoch programs in it, the epoch program
included (seventeen by PR 22's reading; a fit loop of one epoch program,
two evaluations and one pull would read 3).  The `boundary_spans:` line
counts them by program name and by host phase."""

from benchmark import boundary_spans


def read(run):
    return boundary_spans.metric(run, "boundary_programs")
