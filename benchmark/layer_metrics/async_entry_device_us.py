"""Microseconds of device self time per run of Hogwild's `jit_kstep` under
no scope of the step itself (`dsgd.draw` ... `dsgd.update`): what the
program does on entry and exit around its k local steps, first device.
Today the compiler's two copies of the worker's whole shard."""

from benchmark import program_spans


def read(run):
    kstep = program_spans.part(run, "kstep")
    if not kstep or not kstep["scoped"]:
        return None
    return kstep["entry_us_per_run"]
