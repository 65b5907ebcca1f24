"""paged_attention_roofline: the fused paged-attention decode kernel's
share of its roofline over the traced stretch (see `benchlib/roofline.py`); one
call per layer per decode step, its rows the live decode rows."""
from benchlib.roofline import roofline_share


def read(run, name):
    calls = [{"keys": s.decode_keys} for s in run.traced_steps()
             if s.decode_keys]
    return roofline_share(run, "paged_attention", calls)
