"""The least time the chip could take for what a step NEEDS when its
weights carry an output axis (`benchmark/algorithmic_rows.py`: 4 K B P C
flops; the rows drawn with their labels and 12 C bytes a DISTINCT feature
id of the step, expected under the generator's law; no term in the feature
count) over the device time one step of the compiled epoch took in the
trace (`reduce_trace.steps_of`: inside the epoch program alone, worst
device, what `entry_step_roofline` divides by).  A step that moves a
weight row an ENTRY reads a lower share of the same count.  Dense rows, an
engine record without an output count (a program or a cell without the
output axis), no trace: nothing to read."""

from benchmark import algorithmic_rows


def read(run):
    if run.trace is None or run.ctx.peaks is None:
        return None
    program = run.trace["devices"][run.trace["worst_device"]].get("program")
    step = program and program.get("step")
    e = run.engine
    if not step or "n_outputs" not in e or "virtual_workers" not in e or e.get("dense"):
        return None
    shape = (e["batch_size"], e["virtual_workers"], e["row_width"], e["n_outputs"])
    needed = algorithmic_rows.least_seconds(
        algorithmic_rows.step_flops(*shape),
        algorithmic_rows.step_bytes(*shape, e["n_features"], e.get("label_bytes", 1)),
        run.ctx.peaks)
    return 100.0 * needed / step["seconds"]
