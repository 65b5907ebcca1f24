"""Operations and bytes of one call of the fused paged chunked-prefill
kernel (`kernels/paged_prefill.py`), one layer, from its shapes.

A call prefills t real tokens of one row starting at position s: query
i (0 <= i < t) attends causally over s + i + 1 keys, the pool-resident
prefix plus the chunk. With NQ query heads, NKV KV heads and head size H:

    flops = 4 * NQ * H * (t * s + t * (t + 1) / 2)
    bytes = s * NKV * (2 * H * kv_bytes + 2 * scale_bytes)    prefix read
          + t * NKV * (2 * H * kv_bytes + 2 * scale_bytes)    chunk K/V written
          + t * NKV * 2 * H * act_bytes                       chunk K/V read
          + t * 2 * NQ * H * act_bytes                        q in, out
    (int8 codes with one float32 scale per slot and KV head; bfloat16
    activations). Padding of the chunk to the prefill budget is not work.
"""

# Substrings of the kernel's device operation names in the trace.
NAMES = ("paged_prefill",)


def cost(m: dict, call: dict) -> tuple:
    """(flops, bytes) of one layer's call; `call` has "start" and "len"."""
    nq, nkv, h = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    kv_bytes, scale_bytes = (1, 4) if m["serving"]["kv_int8"] else (2, 0)
    s, t = call["start"], call["len"]
    flops = 4 * nq * h * (t * s + t * (t + 1) / 2)
    slot = nkv * (2 * h * kv_bytes + 2 * scale_bytes)
    nbytes = (s + t) * slot + t * nkv * 2 * h * 2 + t * 2 * nq * h * 2
    return float(flops), float(nbytes)
