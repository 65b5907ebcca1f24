"""decode_device_ms.*: device time of one call of the decode program,
mean over the traced stretch: the durations of its "XLA Modules" events
(the jitted `decode_step`) in the profiler trace."""

PROGRAM = "decode_step"


def read(run, name):
    if run.trace is None:
        return None
    calls = [d for n, _, d in run.trace.modules if PROGRAM in n]
    if not calls:
        return None
    return 1e-6 * sum(calls) / len(calls)
