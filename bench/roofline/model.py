"""Operations the model requires for the tokens a window served, from the
configuration: the work of `mfu.*`, independent of how the program runs it.

Per token through the layers: 2 * P flops, P the layer matrices'
parameters (L * (d*NQ*H + 2*d*NKV*H + NQ*H*d + 3*d*f) for a SwiGLU block).
Attention: 4 * L * NQ * H flops per key a token attends to (q.k and
p.v). Head: 2 * d * V flops per logits row, one row per emitted token.
Norms, rotary and sampling are left out (under 1% at these widths).
"""


def layer_params(m: dict) -> int:
    d, f, h = m["d_model"], m["d_ff"], m["head_dim"]
    nq, nkv = m["n_heads"], m["n_kv_heads"]
    return m["num_layers"] * (2 * d * nq * h + 2 * d * nkv * h + 3 * d * f)


def flops(m: dict, tokens: int, keys: int, logits_rows: int) -> float:
    """`tokens` run through the layers, attending to `keys` keys in all,
    and `logits_rows` rows of the head."""
    attn = 4 * m["num_layers"] * m["n_heads"] * m["head_dim"] * keys
    head = 2 * m["d_model"] * m["vocab"] * logits_rows
    return float(2 * layer_params(m) * tokens + attn + head)
