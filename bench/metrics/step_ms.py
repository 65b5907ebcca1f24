"""step_ms.*: mean wall time of one scheduler `step()` over the window,
from the harness's host-clock span around each call. The step ends in a
host pull of the sampled tokens, so the span covers the device's work."""


def read(run, name):
    spans = [s.t1 - s.t0 for s in run.steps]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
