"""Microseconds a step of the compiled epoch spends turning the label
lists it has drawn into the rows of +1 / -1 / 0 its loss takes: the self
time of the operations under the scope `dsgd.labels`
(`models/linear.expand_labels`, called by `BoundSync._one_step` /
`_sparse_step`) inside the epoch program over the steps the traced window
holds, first device.  A program without the scope (dense labels, a commit
before label lists), a trace without any `dsgd.*` scope: nothing to read."""

from benchmark import program_spans

SCOPE = "dsgd.labels"


def read(run):
    program = program_spans.part(run, "program")
    if not program or SCOPE not in program.get("us_per_step", {}):
        return None
    return program_spans.scope_us_per_step(run, (SCOPE,))
