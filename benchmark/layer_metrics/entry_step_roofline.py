"""The least time the chip could take for the bytes a sparse step needs
under a regulariser that is linear in `w` (`benchmark/algorithmic_entries.py`:
the rows drawn, 12 B an entry for the gather, 16 B an entry for the
update's read-modify-write; no term in the feature count) over the device
time one step of the compiled epoch took in the trace
(`reduce_trace.steps_of`: inside the epoch program alone, worst device,
what `sparse_step_roofline` divides by).  A step that passes over all of
`w` reads a lower share of the same count.  Dense rows, an engine record
without the step's shape, no trace: nothing to read."""

from benchmark import algorithmic_entries


def read(run):
    if run.trace is None or run.ctx.peaks is None:
        return None
    program = run.trace["devices"][run.trace["worst_device"]].get("program")
    step = program and program.get("step")
    e = run.engine
    if not step or "virtual_workers" not in e or e.get("dense"):
        return None
    needed = algorithmic_entries.step_bytes(
        e["batch_size"], e["virtual_workers"], e["row_width"])
    return 100.0 * algorithmic_entries.least_seconds(needed, run.ctx.peaks) / step["seconds"]
