"""device_ms.<scope>: device ms a step of the operations in one of the
program's device scopes (`jax.named_scope`), over the traced stretch: the
leaf operations that start inside the program's `serving.step` spans, by
the innermost scope on their op-name path (`trace.op_scope`), summed and
divided by the number of those spans. `device_ms.unscoped` takes the
operations in no scope; with every scope's reading it sums to the
device's busy time inside the step spans."""
from benchlib import trace

UNSCOPED = "unscoped"


def read(run, name):
    if run.trace is None:
        return None
    by_step = trace.step_scopes(run.trace)
    scope = name.split(".", 1)[1]
    key = None if scope == UNSCOPED else scope
    if by_step is None or key not in by_step["ms"]:
        return None
    return by_step["ms"][key]
