"""Microseconds of a step of the compiled epoch under NO `dsgd.*` scope:
the epoch program's busy time that no named piece accounts for, per step,
first device.  Loop control, the compiler's layout copies of the resident
rows (once an epoch, so it weighs most where epochs are short), and
anything a later PR adds without a name.  None on a commit whose program
has no scopes at all (everything would be unscoped there)."""

from benchmark import program_spans


def read(run):
    return program_spans.scope_us_per_step(run, (program_spans.UNSCOPED,))
