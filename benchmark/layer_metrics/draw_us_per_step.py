"""Microseconds a step of the compiled epoch spends drawing its batch: the
self time of the operations under the scope `dsgd.draw` (the sampler's ids
and the three gathers of resident rows, `BoundSync._one_step`) inside the
epoch program over the steps the traced window holds, first device.  Read
by the scope's name (`benchmark/program_spans.py`), so it keeps its meaning
when a PR renumbers the fusions."""

from benchmark import program_spans


def read(run):
    return program_spans.scope_us_per_step(run, ("dsgd.draw",))
