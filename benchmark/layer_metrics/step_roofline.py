"""The least time the chip could take for one step's algorithmic work
(`benchmark/algorithmic.py`: flops and bytes from B, K, D and the row
width, against `benchmark/peaks.py`) over the device time one step of the
compiled epoch took in the trace (`reduce_trace.steps_of`: inside the
epoch program alone, worst device).  HBM bounds both configurations of this benchmark."""

from benchmark import algorithmic


def read(run):
    if run.trace is None or run.ctx.peaks is None:
        return None
    program = run.trace["devices"][run.trace["worst_device"]].get("program")
    step = program and program.get("step")
    e = run.engine
    if not step or "virtual_workers" not in e:
        return None
    work = algorithmic.step_work(
        e["batch_size"], e["virtual_workers"], e["n_features"],
        e["row_width"], e["dense"])
    least = algorithmic.least_step_seconds(work, run.ctx.peaks)
    return 100.0 * least["seconds"] / step["seconds"]
