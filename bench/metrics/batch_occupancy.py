"""batch_occupancy.*: share of the decode batch's slots that decoded, over
the traced stretch, in percent: the `rows` of the program's
`serving.decode` spans, summed, over the number of its `serving.step`
spans times the configuration's `max_batch` (a step that decodes nothing
counts as empty)."""
from benchlib import trace


def read(run, name):
    if run.trace is None:
        return None
    steps = trace.program_spans(run.trace, "step")
    if not steps:
        return None
    rows = sum(s[3]["rows"] for s in trace.program_spans(run.trace, "decode"))
    return 100.0 * rows / (len(steps) * run.m["serving"]["max_batch"])
