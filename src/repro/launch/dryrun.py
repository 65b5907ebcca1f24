import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST precede every other import: jax locks the device
# count at first initialization, and the multi-pod dry-run needs 512
# placeholder host devices to build the production mesh. A CPU-only tool:
# run it with JAX_PLATFORMS=cpu, never on the chip path.

"""Multi-pod dry-run: AOT lower + compile every (arch × shape × mesh) cell.

For each cell this driver:
  1. builds the production mesh (16×16 single-pod or 2×16×16 multi-pod),
  2. resolves parameter / optimizer / batch / cache shardings from the
     partition rules (DP/TP/FSDP/EP),
  3. lowers the appropriate step (train_step / prefill / decode_step) with
     ShapeDtypeStruct inputs — no allocation anywhere,
  4. compiles, prints memory_analysis() and cost_analysis(),
  5. extracts the three roofline terms (compute / memory / collective) and
     appends a JSON record to --out.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b \
      --shape train_4k --mesh single [--quant w4a8] [--out results.jsonl]
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, applicable, get_config
from repro.configs.base import TrainConfig
from repro.core.quant import QuantConfig
from repro.core.quantized_linear import quantize_params_for_serving
from repro.launch.mesh import batch_axes_of, make_production_mesh
from repro.models import build_model
from repro.optim import adamw
from repro.parallel import sharding as shlib
from repro.roofline import analysis as roof
from repro.train.loop import TrainState, make_train_step, train_state_shardings


def _parse_quant(s: str):
    """e.g. w4a8, w2a4, w8a8, w4a8r10 (r10 = 10% 8-bit filter group).

    One grammar for quant tokens everywhere: delegates to the policy
    module's parser (a bare token is just a uniform policy's default)."""
    if not s or s == "none":
        return None
    from repro.core.precision import parse_quant_token

    return parse_quant_token(s)


def _parse_overrides(items):
    """key=value model-config overrides (ints/floats/bools auto-coerced)."""
    out = {}
    for item in items or ():
        key, _, val = item.partition("=")
        if val.lower() in ("true", "false"):
            out[key] = val.lower() == "true"
        else:
            try:
                out[key] = int(val)
            except ValueError:
                try:
                    out[key] = float(val)
                except ValueError:
                    out[key] = val
    return out


def lower_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    quant: str = "none",
    fsdp: bool | None = None,
    microbatches: int = 1,
    remat: bool | None = None,
    overrides: dict | None = None,
    verbose: bool = True,
):
    """Lower + compile one cell; returns the result record dict."""
    import dataclasses

    cfg = get_config(arch)
    qcfg = _parse_quant(quant)
    ov = dict(overrides or {})
    if fsdp is not None:
        ov["fsdp"] = fsdp
    if remat is not None:
        ov["remat"] = remat
    if ov:
        cfg = dataclasses.replace(cfg, **ov)
    shape = SHAPES[shape_name]
    ok, reason = applicable(arch, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    baxes = batch_axes_of(mesh)
    shlib.set_mesh_context(mesh, baxes)
    model = build_model(cfg)
    specs = model.input_specs(shape)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "quant": quant, "chips": chips, "microbatches": microbatches,
        "fsdp": cfg.fsdp, "remat": cfg.remat,
    }

    t0 = time.time()
    key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params_shape = jax.eval_shape(model.init, key_spec)
    param_shardings = shlib.make_param_shardings(params_shape, mesh, cfg.fsdp)
    batch_shardings = jax.tree_util.tree_map(
        lambda s: shlib.batch_sharding(mesh, s, baxes), specs
    )

    if shape.kind == "train":
        tc = TrainConfig(microbatches=microbatches)
        step = make_train_step(model, tc)
        state_shape = jax.eval_shape(
            lambda p: TrainState(p, adamw.init_state(p), None), params_shape
        )
        state_shardings = train_state_shardings(params_shape, tc, mesh,
                                                cfg.fsdp)
        jitted = jax.jit(
            step,
            in_shardings=(state_shardings, batch_shardings),
            out_shardings=(state_shardings, None),
            donate_argnums=(0,),
        )
        with mesh:
            lowered = jitted.lower(state_shape, specs)
    elif shape.kind == "prefill":
        if qcfg is not None:
            params_shape = jax.eval_shape(
                lambda p: quantize_params_for_serving(p, qcfg), params_shape
            )
            param_shardings = shlib.make_param_shardings(params_shape, mesh, cfg.fsdp)

        def prefill_fn(params, batch):
            return model.prefill(params, batch)

        jitted = jax.jit(prefill_fn, in_shardings=(param_shardings, batch_shardings))
        with mesh:
            lowered = jitted.lower(params_shape, specs)
    else:  # decode
        if qcfg is not None:
            params_shape = jax.eval_shape(
                lambda p: quantize_params_for_serving(p, qcfg), params_shape
            )
            param_shardings = shlib.make_param_shardings(params_shape, mesh, cfg.fsdp)
        cache_shape = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len)
        )
        cache_shardings = shlib.cache_shardings(mesh, cache_shape, baxes)

        def decode_fn(params, cache, tokens):
            return model.decode_step(params, cache, tokens)

        jitted = jax.jit(
            decode_fn,
            in_shardings=(param_shardings, cache_shardings, batch_shardings["tokens"]),
            out_shardings=(cache_shardings, None),
            donate_argnums=(1,),
        )
        with mesh:
            lowered = jitted.lower(params_shape, cache_shape, specs["tokens"])

    # Analytic parameter-byte accounting (the kernel-contract HBM view for
    # quantized weights: packed bytes are what a TPU kernel actually reads).
    def _tree_bytes(tree):
        total = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            n = 1
            for s in leaf.shape:
                n *= s
            total += n * jnp.dtype(leaf.dtype).itemsize
        return total

    rec["params_bytes"] = _tree_bytes(params_shape)
    rec["lower_s"] = round(time.time() - t0, 2)
    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 2)

    mf = roof.model_flops(cfg, shape.kind, shape.seq_len, shape.global_batch)
    hlo_text = compiled.as_text()
    report = roof.analyze(compiled, chips, mf, hlo_text=hlo_text)
    rec.update(report.as_dict())
    rec["status"] = "ok"
    rec["hlo_bytes"] = len(hlo_text)

    if verbose:
        try:
            print(compiled.memory_analysis())
        except Exception as e:  # CPU backend may not implement it
            print(f"memory_analysis unavailable: {e}")
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        print({k: ca[k] for k in sorted(ca)[:8]})
        print(
            f"[{arch} × {shape_name} × {rec['mesh']}] "
            f"compute={report.compute_s*1e3:.2f}ms memory={report.memory_s*1e3:.2f}ms "
            f"collective={report.collective_s*1e3:.2f}ms → {report.bottleneck}-bound; "
            f"useful-flops={report.useful_flops_ratio:.2f}"
        )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--quant", default="none")
    ap.add_argument("--fsdp", default=None, choices=[None, "on", "off"])
    ap.add_argument("--remat", default=None, choices=[None, "on", "off"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--override", action="append", default=None,
                    help="ModelConfig override key=value (repeatable)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    try:
        rec = lower_cell(
            args.arch,
            args.shape,
            multi_pod=args.mesh == "multi",
            quant=args.quant,
            fsdp=None if args.fsdp is None else args.fsdp == "on",
            remat=None if args.remat is None else args.remat == "on",
            microbatches=args.microbatches,
            overrides=_parse_overrides(args.override),
        )
        if args.override:
            rec["overrides"] = args.override
    except Exception:
        rec = {
            "arch": args.arch, "shape": args.shape, "mesh": args.mesh,
            "quant": args.quant, "status": "error",
            "error": traceback.format_exc()[-2000:],
        }
        print(rec["error"])
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
    print(json.dumps({k: v for k, v in rec.items() if k != "error"}, default=str))
    return 0 if rec.get("status") in ("ok", "skipped") else 1


if __name__ == "__main__":
    raise SystemExit(main())
