"""Microseconds a step of the compiled epoch spends in FTRL-Proximal's own
arithmetic: the self time of the operations under the scope `dsgd.ftrl`
(ops/ftrl.py: the closed form of w from (z, n) at the margins, the update of
the touched state rows in the scatter's ending) inside the epoch program
over the steps the traced window holds, first device.  A program without
the scope (another optimizer, a commit before FTRL), a trace without any
`dsgd.*` scope: nothing to read."""

from benchmark import program_spans

SCOPE = "dsgd.ftrl"


def read(run):
    program = program_spans.part(run, "program")
    if not program or SCOPE not in program.get("us_per_step", {}):
        return None
    return program_spans.scope_us_per_step(run, (SCOPE,))
