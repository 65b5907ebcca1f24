"""Production training driver.

Wires the full stack: arch config → model → mesh + partition rules →
sharded train state → data pipeline (host-sharded) → fault-tolerant loop
(checkpoint/resume, straggler monitor, preemption saves).

On a real TPU pod this runs under `jax.distributed.initialize()` with one
process per host; in this CPU container it exercises the identical code
path on a 1-device mesh (or a fake multi-device mesh via
--fake-devices N, which must be set before jax initializes — hence the
env-var handling at the top).

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
      --steps 50 --ckpt /tmp/ckpt [--fake-devices 4 --mesh-shape 2,2]
"""
import argparse
import os
import sys


def _preparse_fake_devices():
    if "--fake-devices" in sys.argv:
        n = sys.argv[sys.argv.index("--fake-devices") + 1]
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n} "
            + os.environ.get("XLA_FLAGS", "")
        )


_preparse_fake_devices()

import jax  # noqa: E402  (after XLA_FLAGS)
import jax.numpy as jnp  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--qat", default=None)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--fake-devices", type=int, default=0)
    ap.add_argument("--mesh-shape", default=None,
                    help="data,model (e.g. 2,2); default: all devices on data")
    args = ap.parse_args()

    from repro.checkpoint import CheckpointManager
    from repro.configs import get_config, get_reduced_config
    from repro.configs.base import TrainConfig
    from repro.data import DataIterator
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.optim import adamw
    from repro.parallel import sharding as sh
    from repro.train.loop import TrainState, init_train_state, make_train_step, run_training

    enable_compile_cache()
    cfg = (get_reduced_config if args.reduced else get_config)(args.arch)
    if args.qat:
        from repro.launch.dryrun import _parse_quant

        cfg = cfg.with_quant(_parse_quant(args.qat))
    model = build_model(cfg)

    n_dev = len(jax.devices())
    if args.mesh_shape:
        dshape = tuple(int(x) for x in args.mesh_shape.split(","))
    else:
        dshape = (n_dev, 1)
    mesh = make_mesh(dshape, ("data", "model"))
    sh.set_mesh_context(mesh, ("data",))
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}, "
          f"arch: {cfg.name} ({cfg.param_count()/1e6:.1f}M params)")

    tc = TrainConfig(
        lr=args.lr, warmup_steps=min(20, args.steps // 5), total_steps=args.steps,
        microbatches=args.microbatches,
        grad_compress_bits=8 if args.compress else 0,
        log_every=max(1, args.steps // 20),
        checkpoint_every=max(1, args.steps // 3),
    )
    # Host sharding: in multi-process deployments each host materializes
    # its slice; single-process here → host 0 of 1.
    data = DataIterator(cfg, global_batch=args.global_batch, seq_len=args.seq,
                        seed=tc.seed, host_id=jax.process_index(),
                        host_count=jax.process_count(), branch=8)
    mgr = CheckpointManager(args.ckpt, keep=2, async_save=True) if args.ckpt else None

    def hook(step, rec):
        print(f"step {rec['step']:5d}  loss {rec['loss']:.4f}  "
              f"gnorm {rec['grad_norm']:.2f}  {rec['dt']*1e3:.0f} ms"
              + ("  [STRAGGLER]" if rec.get("straggler") else ""))

    with mesh:
        state, history = run_training(model, tc, data, checkpoint_mgr=mgr,
                                      hooks=hook)
    if mgr:
        mgr.wait()
    print(f"done: {len(history)} logged steps, "
          f"final loss {history[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
