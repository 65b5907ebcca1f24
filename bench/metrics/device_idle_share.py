"""device_idle_share.*: share of the time the scheduler had work (inside
the harness's `bench.step` spans of the traced stretch) in which no
operation ran on the device, in percent, from the profiler trace."""


def read(run, name):
    from benchlib import trace

    if run.trace is None:
        return None
    busy = trace.busy_share_of(run.trace, trace.host_spans(run.trace, "step"))
    return None if busy is None else 100.0 * (1.0 - busy)
