"""The least time the chip could take for the bytes a whole sparse step
needs under an `l2` regulariser (`benchmark/algorithmic_sparse.py`: the
rows drawn, the entries' traffic of gather and scatter, w read and written
once) over the device time one step of the compiled epoch took in the
trace (`reduce_trace.steps_of`: inside the epoch program alone, worst
device — what `step_roofline` divides by).  Dense rows: nothing to read."""

from benchmark import algorithmic_sparse


def read(run):
    if run.trace is None or run.ctx.peaks is None:
        return None
    program = run.trace["devices"][run.trace["worst_device"]].get("program")
    step = program and program.get("step")
    e = run.engine
    if not step or "virtual_workers" not in e or e.get("dense"):
        return None
    needed = algorithmic_sparse.step_bytes(
        e["batch_size"], e["virtual_workers"], e["n_features"], e["row_width"])
    return 100.0 * algorithmic_sparse.least_seconds(needed, run.ctx.peaks) / step["seconds"]
