"""Microseconds a step of the compiled epoch spends applying the update:
self time under the scopes `dsgd.regularize` (the regulariser's term on a
gradient), `dsgd.update` (the mean and `w - lr * g`; in a step that scatters
its entries into the carried weights, the scalar that stands for the
regulariser) and `dsgd.rescale` (that scalar folded into the weights, once
an epoch program) inside the epoch program per step, first device.  A
program without one of the scopes adds nothing for it; a trace without any
`dsgd.*` scope: nothing to read."""

from benchmark import program_spans


def read(run):
    return program_spans.scope_us_per_step(
        run, ("dsgd.regularize", "dsgd.update", "dsgd.rescale"))
