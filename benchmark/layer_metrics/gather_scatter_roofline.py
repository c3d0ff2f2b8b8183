"""The least time the chip could take for the bytes one step's stored
entries move (`benchmark/algorithmic_sparse.py`: index, value and a word
of w for the gather; the same and a write-back for the scatter) over the
device time a step spends under the scopes `dsgd.margins` and
`dsgd.scatter` (with `dsgd.onehot` and `dsgd.coeff`, as
`margins_us_per_step` and `scatter_us_per_step` read them) inside the
epoch program, first device.  A trace without scopes, an engine record
without the step's shape, or dense rows: nothing to read."""

from benchmark import algorithmic_sparse, program_spans


def read(run):
    e = run.engine
    if run.ctx.peaks is None or "virtual_workers" not in e or e.get("dense"):
        return None
    spent = program_spans.scope_us_per_step(
        run, ("dsgd.margins", "dsgd.onehot", "dsgd.scatter", "dsgd.coeff"))
    if not spent:
        return None
    moved = algorithmic_sparse.gather_scatter_bytes(
        e["batch_size"], e["virtual_workers"], e["row_width"])
    least = algorithmic_sparse.least_seconds(moved["gather"] + moved["scatter"], run.ctx.peaks)
    return 100.0 * least / (spent * 1e-6)
