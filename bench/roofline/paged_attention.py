"""Operations and bytes of one call of the fused paged-attention decode
kernel (`kernels/paged_attention.py`), one layer, from its shapes.

A call serves the live decode rows of a step. Row b holds c_b keys (its
position plus one). With NQ query heads, NKV KV heads and head size H:

    flops = sum_b 4 * NQ * H * c_b
            (q.k over c_b keys and p.v over c_b values, 2 flops a MAC)
    bytes = sum_b c_b * NKV * (2 * H * kv_bytes + 2 * scale_bytes)
            + rows * 2 * NQ * H * act_bytes
            (int8 K and V codes with one float32 scale each per slot and
            KV head, read once; the bfloat16 query in and output out)

Rows that are free or still prefilling cost no work and are not counted.
"""

# Substrings of the kernel's device operation names in the trace.
NAMES = ("paged_attention",)


def cost(m: dict, call: dict) -> tuple:
    """(flops, bytes) of one layer's call; `call["keys"]` lists c_b."""
    nq, nkv, h = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    kv_bytes, scale_bytes = (1, 4) if m["serving"]["kv_int8"] else (2, 0)
    keys = call["keys"]
    flops = 4 * nq * h * sum(keys)
    nbytes = (sum(keys) * nkv * (2 * h * kv_bytes + 2 * scale_bytes)
              + len(keys) * 2 * nq * h * 2)
    return float(flops), float(nbytes)
