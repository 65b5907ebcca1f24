"""prefill_plans.*: chunked-prefill plans in flight a step, over the traced
stretch: the `plans` argument of the program's `serving.chunk` spans (at
most one a step), summed, over the number of its `serving.step` spans (a
step that runs no chunk has none in flight)."""
from benchlib import trace


def read(run, name):
    if run.trace is None:
        return None
    steps = trace.program_spans(run.trace, "step")
    if not steps:
        return None
    plans = sum(s[3]["plans"] for s in trace.program_spans(run.trace, "chunk"))
    return plans / len(steps)
