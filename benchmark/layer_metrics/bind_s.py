"""Seconds from the call of `fit` to the program's own log record that
follows its bind of the rows (`dsgd.trainer` "train split: ...",
`dsgd.hogwild` "hogwild kernel=..."), caught by the benchmark's log tap.
Reported where the bind is work: Hogwild gathers a shard per worker and
the test split (0.5 s).  The synchronous bind of rows that already lie on
the device in whole chunks is a `device_put` of a device array (2 ms) and
guards nothing, so the sync cells do not list this metric."""


def read(run):
    return run.ctx.setup.get("bind_s")
