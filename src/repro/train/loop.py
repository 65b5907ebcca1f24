"""Training loop: step factory (used by the dry-run and the live driver),
gradient-accumulation microbatching, int8-compressed data-parallel gradients
with error feedback, and a fault-tolerant runner (checkpoint/resume,
straggler monitor, preemption-safe saves).
"""
from __future__ import annotations

import dataclasses
import signal
import time
from typing import Any, Callable, Iterator, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import TrainConfig
from repro.optim import adamw
from repro.parallel import collectives
from repro.parallel import sharding as shlib


class TrainState(NamedTuple):
    params: Any
    opt: adamw.AdamState
    err: Any            # error-feedback buffers (None when compression off)


def init_train_state(params, tc: TrainConfig) -> TrainState:
    err = collectives.init_error(params) if tc.grad_compress_bits else None
    return TrainState(params=params, opt=adamw.init_state(params), err=err)


def train_state_shardings(params_shape, tc: TrainConfig, mesh,
                          fsdp: bool) -> TrainState:
    """NamedSharding tree of a TrainState on `mesh`: the parameters, both
    Adam moments and the error-feedback buffers follow the partition
    rules (`parallel.sharding`); the step count is replicated."""
    pshard = shlib.make_param_shardings(params_shape, mesh, fsdp)
    return TrainState(
        params=pshard,
        opt=adamw.AdamState(step=shlib.replicated(mesh), mu=pshard,
                            nu=pshard),
        err=pshard if tc.grad_compress_bits else None,
    )


def init_sharded_train_state(model, tc: TrainConfig, mesh, key) -> TrainState:
    """Initialize the train state directly in its sharded layout: each
    device materializes only its own shards, so a model whose full state
    does not fit one device still starts."""
    shardings = train_state_shardings(jax.eval_shape(model.init, key), tc,
                                      mesh, model.cfg.fsdp)
    return jax.jit(lambda k: init_train_state(model.init(k), tc),
                   out_shardings=shardings)(key)


def make_train_step(model, tc: TrainConfig) -> Callable:
    """Returns train_step(state, batch) → (state, metrics).

    microbatches > 1 splits the batch on axis 0 and accumulates grads with
    a lax.scan — the activation-memory knob (remat already bounds per-layer
    memory; microbatching bounds the batch dimension).
    """
    lr_fn = adamw.cosine_schedule(tc)

    def loss_fn(params, batch):
        loss, metrics = model.train_loss(params, batch)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads(params, batch):
        if tc.microbatches <= 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, grads

        def split(x):
            b = x.shape[0]
            mb = b // tc.microbatches
            return x.reshape(tc.microbatches, mb, *x.shape[1:])

        micro = jax.tree_util.tree_map(split, batch)
        zero_g = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )

        def acc_step(carry, mb):
            g_acc, l_acc = carry
            (loss, _), g = grad_fn(params, mb)
            g_acc = jax.tree_util.tree_map(
                lambda a, b_: a + b_.astype(jnp.float32), g_acc, g
            )
            return (g_acc, l_acc + loss), None

        (g_sum, l_sum), _ = jax.lax.scan(acc_step, (zero_g, 0.0), micro)
        inv = 1.0 / tc.microbatches
        grads = jax.tree_util.tree_map(lambda g: g * inv, g_sum)
        loss = l_sum * inv
        return loss, {"loss": loss, "aux_loss": jnp.zeros((), jnp.float32)}, grads

    def train_step(state: TrainState, batch):
        loss, metrics, grads = compute_grads(state.params, batch)
        grads, gnorm = adamw.clip_by_global_norm(grads, tc.grad_clip)
        err = state.err
        if tc.grad_compress_bits:
            _, err, grads = collectives.compress_gradients(
                grads, err, bits=tc.grad_compress_bits
            )
        params, opt, lr = adamw.apply_updates(
            state.params, grads, state.opt, tc, lr_fn
        )
        metrics = dict(metrics)
        metrics.update(grad_norm=gnorm, lr=lr, loss=loss)
        return TrainState(params=params, opt=opt, err=err), metrics

    return train_step


# --------------------------------------------------------------------------
# Fault-tolerant runner
# --------------------------------------------------------------------------


@dataclasses.dataclass
class StragglerMonitor:
    """EWMA step-time monitor. On TPU pods stragglers manifest as step-time
    blowups on the whole SPMD program; the launcher contract is
    flag → checkpoint → evict → restart. Here we detect and log."""

    alpha: float = 0.1
    threshold: float = 2.5
    ewma: Optional[float] = None
    flagged: int = 0

    def observe(self, dt: float) -> bool:
        if self.ewma is None:
            self.ewma = dt
            return False
        slow = dt > self.threshold * self.ewma
        self.ewma = (1 - self.alpha) * self.ewma + self.alpha * dt
        if slow:
            self.flagged += 1
        return slow


class _PreemptionFlag:
    """SIGTERM → finish the current step, checkpoint, exit cleanly."""

    def __init__(self):
        self.raised = False
        try:
            signal.signal(signal.SIGTERM, self._handle)
        except ValueError:  # non-main thread (tests)
            pass

    def _handle(self, *_):
        self.raised = True


def run_training(
    model,
    tc: TrainConfig,
    data_iter: Iterator,
    checkpoint_mgr=None,
    init_key=None,
    hooks: Optional[Callable[[int, dict], None]] = None,
    jit: bool = True,
):
    """End-to-end training with restore-if-present, periodic + preemption
    checkpoints, and straggler monitoring. Returns (state, history)."""
    init_key = init_key if init_key is not None else jax.random.PRNGKey(tc.seed)
    mesh = shlib.get_mesh()
    start_step = 0
    if checkpoint_mgr is not None and checkpoint_mgr.latest_step() is not None:
        state, data_state, start_step = checkpoint_mgr.restore(
            lambda: init_train_state(model.init(init_key), tc)
        )
        if mesh is not None:
            state = jax.device_put(state, train_state_shardings(
                jax.eval_shape(model.init, init_key), tc, mesh,
                model.cfg.fsdp))
        if data_state is not None and hasattr(data_iter, "set_state"):
            data_iter.set_state(data_state)
    elif mesh is not None:
        state = init_sharded_train_state(model, tc, mesh, init_key)
    else:
        state = init_train_state(model.init(init_key), tc)

    step_fn = make_train_step(model, tc)
    if jit:
        step_fn = jax.jit(step_fn, donate_argnums=(0,))

    monitor = StragglerMonitor()
    preempt = _PreemptionFlag()
    history = []
    for step in range(start_step, tc.total_steps):
        batch = next(data_iter)
        t0 = time.perf_counter()
        state, metrics = step_fn(state, batch)
        jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        slow = monitor.observe(dt)
        if step % tc.log_every == 0 or slow:
            rec = {k: float(v) for k, v in metrics.items()}
            rec.update(step=step, dt=dt, straggler=slow)
            history.append(rec)
            if hooks:
                hooks(step, rec)
        should_ckpt = checkpoint_mgr is not None and (
            (step + 1) % tc.checkpoint_every == 0 or preempt.raised
        )
        if should_ckpt:
            data_state = data_iter.get_state() if hasattr(data_iter, "get_state") else None
            checkpoint_mgr.save(step + 1, state, data_state)
        if preempt.raised:
            break
    return state, history
