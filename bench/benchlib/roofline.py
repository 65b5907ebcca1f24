"""The arithmetic of the `<kernel>_roofline` readers: the least time
the chip could take for the calls made while the trace ran (operations
over the bf16 peak or bytes over HBM bandwidth, whichever is larger),
over the kernel's summed device time in the trace, in percent."""
import sys

from benchlib import trace


def roofline_share(run, kernel: str, calls) -> "float | None":
    if run.trace is None or not calls:
        return None
    mod = run.roofline(kernel)
    events = [d for _, _, d in trace.matching(run.trace.ops, mod.NAMES)]
    if not events:
        return None
    layers = run.m["num_layers"]
    flops = nbytes = 0.0
    for c in calls:
        f, b = mod.cost(run.m, c)
        flops += f * layers
        nbytes += b * layers
    t_flops = flops / run.peaks["bf16_flops_per_s"]
    t_bytes = nbytes / run.peaks["hbm_bytes_per_s"]
    bound = "memory" if t_bytes >= t_flops else "compute"
    kernel_s = sum(events) * 1e-9
    print(f"[bench] {kernel}: {len(calls)} calls x {layers} layers, "
          f"{len(events)} device events, {kernel_s} s; {flops} flops, "
          f"{nbytes} bytes; {bound}-bound", file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / kernel_s
