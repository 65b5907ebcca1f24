"""paged_prefill_roofline: the fused chunked-prefill kernel's share of its
roofline over the traced stretch (see `benchlib/roofline.py`); one call per layer
per prefill chunk."""
from benchlib.roofline import roofline_share


def read(run, name):
    calls = [c for s in run.traced_steps() for c in s.chunks]
    return roofline_share(run, "paged_prefill", calls)
