"""The least time the chip could take for the bytes a step of FTRL-Proximal
needs (`benchmark/algorithmic_ftrl.py`: the rows drawn, 16 B an entry for
the margins' reads of (z, n), 24 B an entry for the update's
read-modify-write of them; no term in the feature count) over the device
time one step of the compiled epoch took in the trace
(`reduce_trace.steps_of`: inside the epoch program alone, worst device,
what `entry_step_roofline` divides by).  An engine record that does not say
`optimizer` 'ftrl' (another driver, a program without FTRL), dense rows, no
trace: nothing to read."""

from benchmark import algorithmic_ftrl


def read(run):
    if run.trace is None or run.ctx.peaks is None:
        return None
    program = run.trace["devices"][run.trace["worst_device"]].get("program")
    step = program and program.get("step")
    e = run.engine
    if not step or e.get("optimizer") != "ftrl" or "virtual_workers" not in e or e.get("dense"):
        return None
    needed = algorithmic_ftrl.step_bytes(e["batch_size"], e["virtual_workers"], e["row_width"])
    return 100.0 * algorithmic_ftrl.least_seconds(needed, run.ctx.peaks) / step["seconds"]
