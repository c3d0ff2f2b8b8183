"""Smoothed test loss at the first loss check past the cell's
`budget_updates`, as the program logged it.  Judged only through
`correct` (the band in the cell's quality file); recorded here so that a
rate bought with staleness shows beside the rate."""


def read(run):
    return run.fit.get("budget_loss")
