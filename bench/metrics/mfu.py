"""mfu.*: the whole step's share of the chip's bf16 peak, in percent.
Operations the model requires for the tokens the window prefilled and
decoded (the `flops` of the configuration's model module), over the summed
host-clock spans of the window's steps times the bf16 peak."""


def read(run, name):
    busy = sum(s.t1 - s.t0 for s in run.steps)
    if busy <= 0:
        return None
    tokens = keys = rows = 0
    for s in run.steps:
        tokens += len(s.decode_keys) + sum(c["len"] for c in s.chunks)
        keys += sum(s.decode_keys) + sum(
            c["len"] * c["start"] + c["len"] * (c["len"] + 1) // 2
            for c in s.chunks)
        rows += s.logits_rows
    if tokens == 0:
        return None
    ops = run.model.flops(run.m, tokens, keys, rows)
    return 100.0 * ops / (busy * run.peaks["bf16_flops_per_s"])
