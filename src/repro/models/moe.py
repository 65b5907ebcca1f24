"""Mixture-of-Experts FFN with capacity-bounded sort-based dispatch.

Two sharding regimes (config `moe_shard`):
  'expert' (EP): expert dim sharded over 'model' — llama4 (128 % 16 == 0).
                 Dispatch/combine scatter-gathers become all-to-alls under
                 SPMD, the canonical EP communication pattern.
  'ffn'    (TP): expert hidden dim sharded over 'model' — mixtral (8 < 16).

Dispatch: tokens are routed top-k, then *sorted by expert id*; each expert
processes a fixed-capacity block (C = ceil(N·k/E · capacity_factor)), with
overflow dropped (standard Switch-style dropping — keeps the step shape
static, which pjit requires). The router runs in fp32 and contributes the
usual load-balance auxiliary loss.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.parallel.sharding import constrain


def init_moe(key, cfg) -> dict:
    ks = jax.random.split(key, 4)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe_experts
    dt = jnp.dtype(cfg.dtype)
    group = "experts_ep" if cfg.moe_shard == "expert" else "experts_tp"
    glu = cfg.ffn in ("swiglu", "geglu")

    def stack(k, din, dout):
        keys = jax.random.split(k, e)
        return jax.vmap(lambda kk: cm.dense_init(kk, din, dout, dt))(keys)

    experts = {"w_up": stack(ks[0], d, f), "w_down": stack(ks[1], f, d)}
    if glu:
        experts["w_gate"] = stack(ks[2], d, f)
    return {"router": cm.dense_init(ks[3], d, e, jnp.float32), group: experts}


def _expert_ffn(experts: dict, xe: jax.Array, cfg) -> jax.Array:
    """xe: (E, C, d) → (E, C, d) via per-expert FFN (batched einsum)."""
    def mm(a, w):
        return jnp.einsum("ecd,edf->ecf", a, w.astype(a.dtype))

    if cfg.ffn in ("swiglu", "geglu"):
        g = mm(xe, experts["w_gate"])
        u = mm(xe, experts["w_up"])
        act = jax.nn.silu(g) if cfg.ffn == "swiglu" else jax.nn.gelu(g, approximate=True)
        h = act * u
    else:
        h = jnp.square(jax.nn.relu(mm(xe, experts["w_up"])))
    h = constrain(h, "model" if cfg.moe_shard == "expert" else None, None,
                  None if cfg.moe_shard == "expert" else "model")
    return mm(h, experts["w_down"])


def moe_apply_shardmap(params: dict, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """EP dispatch as an explicit shard_map (§Perf cell B resolution).

    XLA SPMD replicates the data-dependent dispatch scatter of
    :func:`moe_apply`, producing multi-TB all-gathers at llama4 scale.
    This path takes dispatch out of SPMD's hands: activations are
    replicated across the `model` axis between layers (the TP layout), so
    every model shard can rout locally, run ONLY its own E/|model| experts
    on a local capacity buffer, and the combine is a single psum over
    `model` of the (N_local, d) output — per-layer wire bytes drop from
    O(E·cap·d) gathers to one activation-sized all-reduce (~86× for
    llama4 train_4k; see EXPERIMENTS.md).

    Requires: an active mesh context, moe_shard='expert', and
    E % |model| == 0. Falls back to moe_apply otherwise.
    """
    from jax.sharding import PartitionSpec as P

    from repro.parallel import sharding as shlib

    mesh = shlib.get_mesh()
    E = cfg.moe_experts
    n_model = dict(zip(mesh.axis_names, mesh.devices.shape))["model"] \
        if mesh is not None and "model" in mesh.axis_names else 0
    # E ≥ axis: each shard owns E/n experts. E < axis (mixtral: 8 on 16):
    # experts replicate across n/E shards, each replica taking a disjoint
    # slice of the expert's capacity — still one psum to combine.
    if (
        mesh is None
        or not n_model
        or cfg.moe_shard != "expert"
        or (E % n_model != 0 and n_model % E != 0)
    ):
        return moe_apply(params, x, cfg)

    baxes = shlib.batch_axes()
    B, T, d = x.shape
    bsize = 1
    for a in baxes:
        bsize *= dict(zip(mesh.axis_names, mesh.devices.shape)).get(a, 1)
    x_spec = P(baxes if B % bsize == 0 else None, None, None)
    experts = params["experts_ep"]
    glu = "w_gate" in experts

    # E ≥ axis: pure EP — each shard owns E/n experts (my0 slice, full f).
    # E < axis: TP-inside-shard_map — every shard keeps ALL experts but
    # only f/n of their hidden dim (weights stay sharded, zero movement);
    # each shard computes partial down-projections for every token and the
    # combine psum reconstructs them exactly (GLU is elementwise in f).
    tp_mode = n_model > E
    E_loc = E if tp_mode else E // n_model

    def body(router, w_up, w_down, w_gate, xs):
        Bl, Tl, _ = xs.shape
        N = Bl * Tl
        K = cfg.moe_top_k
        idx = jax.lax.axis_index("model")
        my0 = jnp.int32(0) if tp_mode else idx * E_loc
        xt = xs.reshape(N, d)
        logits = xt.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        gate_w, gate_idx = jax.lax.top_k(probs, K)
        if K > 1:
            gate_w = gate_w / jnp.sum(gate_w, axis=-1, keepdims=True)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32), axis=0)
        aux = E * jnp.sum(me * ce)

        cap = int(-(-N * K // E) * cfg.moe_capacity_factor)
        cap = max(8, -(-cap // 8) * 8)
        fe = gate_idx.reshape(-1)
        ft = jnp.repeat(jnp.arange(N, dtype=jnp.int32), K)
        fg = gate_w.reshape(-1)
        order = jnp.argsort(fe)
        se, st, sg = fe[order], ft[order], fg[order]
        counts = jnp.bincount(fe, length=E)
        starts = jnp.concatenate(
            [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]]
        )
        pos = jnp.arange(N * K, dtype=jnp.int32) - starts[se].astype(jnp.int32)
        mine = (se >= my0) & (se < my0 + E_loc) & (pos < cap)
        e_loc = jnp.where(mine, se - my0, E_loc)        # OOB → dropped
        p_loc = jnp.where(mine, pos, 0)
        xe = jnp.zeros((E_loc, cap, d), xs.dtype).at[e_loc, p_loc].set(
            xt[st], mode="drop", unique_indices=True
        )

        def mm(a, w):
            # in_specs deliver each shard exactly the slice it computes
            # with: (E_loc, d, f) in EP mode, (E, d, f/n) in TP mode.
            return jnp.einsum("ecd,edf->ecf", a, w.astype(a.dtype))

        if glu:
            g = mm(xe, w_gate)
            u = mm(xe, w_up)
            act = jax.nn.silu(g) if cfg.ffn == "swiglu" else jax.nn.gelu(
                g, approximate=True)
            h = act * u
        else:
            h = jnp.square(jax.nn.relu(mm(xe, w_up)))
        ye = mm(h, w_down)

        got = ye[jnp.where(mine, e_loc, 0), p_loc]
        got = got * mine[:, None] * sg[:, None].astype(xs.dtype)
        out = jnp.zeros((N, d), xs.dtype).at[st].add(got)
        out = jax.lax.psum(out, "model")                # the ONLY collective
        return out.reshape(Bl, Tl, d), aux

    w_gate = experts.get("w_gate", experts["w_up"])  # placeholder if non-GLU
    if tp_mode:
        up_spec = P(None, None, "model")     # (E, d, f/n)
        down_spec = P(None, "model", None)   # (E, f/n, d) → partial sums
    else:
        up_spec = down_spec = P("model", None, None)
    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), up_spec, down_spec, up_spec, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(params["router"], experts["w_up"], experts["w_down"], w_gate, x)
    return out, aux.astype(jnp.float32)


def moe_apply(params: dict, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """x: (B, T, d) → (out (B, T, d), aux_loss scalar)."""
    B, T, d = x.shape
    E, K = cfg.moe_experts, cfg.moe_top_k
    N = B * T
    xt = x.reshape(N, d)

    gate_logits = xt.astype(jnp.float32) @ params["router"]      # (N, E)
    probs = jax.nn.softmax(gate_logits, axis=-1)
    gate_w, gate_idx = jax.lax.top_k(probs, K)                   # (N, K)
    if K > 1:
        gate_w = gate_w / jnp.sum(gate_w, axis=-1, keepdims=True)

    # Load-balance aux loss (Switch): E * sum_e fraction_e * prob_e.
    me = jnp.mean(probs, axis=0)
    one_hot_top = jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32)
    ce = jnp.mean(one_hot_top, axis=0)
    aux = E * jnp.sum(me * ce)

    cap = int(-(-N * K // E) * cfg.moe_capacity_factor)
    cap = max(8, -(-cap // 8) * 8)

    # ---- sort-based dispatch -------------------------------------------
    # NOTE (§Perf cell B, EXPERIMENTS.md): both this flat (E·cap, d)
    # scatter and a 2-D (expert, slot) formulation are replicated by XLA
    # SPMD (data-dependent scatter over the sharded expert dim), producing
    # the all-gathers that make llama4 train collective-bound. The flat
    # form measures ~25% fewer wire bytes, so it is the checked-in
    # variant; the real fix is a shard_map ragged all-to-all dispatch.
    flat_expert = gate_idx.reshape(-1)                            # (N*K,)
    flat_token = jnp.repeat(jnp.arange(N, dtype=jnp.int32), K)
    flat_gate = gate_w.reshape(-1)
    order = jnp.argsort(flat_expert)                              # stable
    se, st, sg = flat_expert[order], flat_token[order], flat_gate[order]
    counts = jnp.bincount(flat_expert, length=E)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(N * K, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    keep = pos_in_e < cap
    dest = jnp.where(keep, se * cap + pos_in_e, E * cap)          # E*cap = drop

    buf = jnp.zeros((E * cap, d), x.dtype).at[dest].set(
        xt[st], mode="drop", unique_indices=True
    )
    xe = buf.reshape(E, cap, d)
    xe = constrain(xe, "model" if cfg.moe_shard == "expert" else None, None, None)

    ye = _expert_ffn(params["experts_ep" if cfg.moe_shard == "expert"
                            else "experts_tp"], xe, cfg)
    ybuf = ye.reshape(E * cap, d)

    # ---- combine --------------------------------------------------------
    gathered = jnp.take(ybuf, jnp.where(keep, dest, 0), axis=0)
    gathered = gathered * keep[:, None] * sg[:, None].astype(x.dtype)
    out = jnp.zeros((N, d), x.dtype).at[st].add(gathered)
    return out.reshape(B, T, d), aux.astype(jnp.float32)
