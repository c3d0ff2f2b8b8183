"""Mean microseconds of a `slave.async.iteration` span minus its
`slave.async.pull`: drain + dispatch + apply + push, the host's part of a
Hogwild dispatch and what paces the cell once the device does not.  Over
the iterations that lie whole inside the traced window, all workers."""

from benchmark import program_spans


def read(run):
    spans = program_spans.part(run, "async")
    if not spans:
        return None
    return spans["iteration_us"] - spans["phase_us"]["slave.async.pull"]
