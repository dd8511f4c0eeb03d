"""Train loop: microbatch accumulation, clipping, compression, checkpoints.

``make_train_step`` builds one jit-able step over a TrainState; ``Trainer``
wraps it with data, checkpointing, auto-resume, and step-time straggler
monitoring.  The same machinery drives LM and vision models (anything with
``loss_fn(params, batch) -> (loss, metrics_dict)``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TrainConfig
from repro.obs import span
from repro.utils import merge_trees, split_trainable
from .checkpoint import CheckpointManager
from .compress import compress_decompress, init_error_feedback
from .optim import clip_by_global_norm, make_optimizer, make_schedule

__all__ = ["TrainState", "make_train_step", "Trainer"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any          # trainable leaves (others None)
    static: Any          # masks / graph factors (non-trainable)
    opt_state: Any
    step: jax.Array
    ef_error: Any = None  # int8-compression error feedback

    def full_params(self):
        return merge_trees(self.params, self.static)


def init_train_state(params, tcfg: TrainConfig) -> TrainState:
    # defensive copy: the step function donates the state, which would
    # otherwise invalidate the caller's params (e.g. across restart drills)
    params = jax.tree_util.tree_map(
        lambda x: None if x is None else jnp.array(x),
        params, is_leaf=lambda x: x is None,
    )
    train, static = split_trainable(params)
    opt = make_optimizer(tcfg)
    state = TrainState(
        params=train,
        static=static,
        opt_state=opt.init(train),
        step=jnp.zeros((), jnp.int32),
    )
    if tcfg.grad_compression == "int8":
        state.ef_error = init_error_feedback(train)
    return state


def make_train_step(
    loss_fn: Callable[[Any, Any], tuple[jax.Array, dict]],
    tcfg: TrainConfig,
):
    """loss_fn(full_params, microbatch) -> (loss, metrics).

    The returned step consumes a batch with a leading microbatch axis
    (n_micro, per_micro, ...) when tcfg.microbatches > 1.
    """
    opt = make_optimizer(tcfg)
    sched = make_schedule(tcfg)

    def grads_of(train, static, batch):
        def f(t):
            loss, metrics = loss_fn(merge_trees(t, static), batch)
            return loss, metrics
        (loss, metrics), g = jax.value_and_grad(f, has_aux=True)(train)
        return loss, metrics, g

    def step_fn(state: TrainState, batch):
        train, static = state.params, state.static
        if tcfg.microbatches > 1:
            def body(acc, mb):
                loss, metrics, g = grads_of(train, static, mb)
                acc_g, acc_loss = acc
                acc_g = jax.tree_util.tree_map(
                    lambda a, b: None if a is None else a + b,
                    acc_g, g, is_leaf=lambda x: x is None,
                )
                return (acc_g, acc_loss + loss), metrics

            zero_g = jax.tree_util.tree_map(
                lambda p: None if p is None else jnp.zeros_like(p, jnp.float32),
                train, is_leaf=lambda x: x is None,
            )
            (g, loss_sum), metrics = jax.lax.scan(
                body, (zero_g, jnp.zeros((), jnp.float32)), batch
            )
            n = tcfg.microbatches
            g = jax.tree_util.tree_map(
                lambda x: None if x is None else x / n,
                g, is_leaf=lambda x: x is None,
            )
            loss = loss_sum / n
            metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics)
        else:
            loss, metrics, g = grads_of(train, static, batch)

        new_ef = state.ef_error
        if tcfg.grad_compression == "int8":
            g, new_ef = compress_decompress(g, state.ef_error)

        if tcfg.grad_clip:
            g, gnorm = clip_by_global_norm(g, tcfg.grad_clip)
        else:
            gnorm = jnp.zeros(())

        lr = sched(state.step)
        with jax.named_scope("optim.update"):
            new_params, new_opt = opt.update(g, state.opt_state, train, lr)
        new_state = TrainState(
            params=new_params,
            static=static,
            opt_state=new_opt,
            step=state.step + 1,
            ef_error=new_ef,
        )
        out_metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr, **metrics}
        return new_state, out_metrics

    return step_fn


class Trainer:
    """Drives the step function: data, checkpoints, resume, stragglers."""

    def __init__(
        self,
        loss_fn,
        init_params,
        tcfg: TrainConfig,
        data_iter,
        *,
        jit: bool = True,
        checkpoint: bool = True,
        hooks: Optional[list] = None,
        plan_fingerprint: Optional[str] = None,
    ):
        self.tcfg = tcfg
        self.data = iter(data_iter)
        self.state = init_train_state(init_params, tcfg)
        step_fn = make_train_step(loss_fn, tcfg)
        self.step_fn = jax.jit(step_fn, donate_argnums=(0,)) if jit else step_fn
        # the sparsity-plan stamp: saved beside weights, checked on restore
        self.ckpt = CheckpointManager(
            tcfg.checkpoint_dir, plan_fingerprint=plan_fingerprint
        ) if checkpoint else None
        self.hooks = hooks or []
        self.history: list[dict] = []
        # straggler watchdog: EMA of step time; steps > 3x EMA are flagged
        self._ema: Optional[float] = None
        self.straggler_events: list[tuple[int, float]] = []
        # <phase>_s / <phase>_calls of the train.* spans in run()
        self.stats: dict = {}

    # -- resume ------------------------------------------------------------
    def try_resume(self) -> Optional[int]:
        if self.ckpt is None:
            return None
        restorable = {
            "params": self.state.params,
            "opt_state": self.state.opt_state,
        }
        tree, meta = self.ckpt.restore(restorable)
        if tree is None:
            return None
        self.state = dataclasses.replace(
            self.state,
            params=tree["params"],
            opt_state=tree["opt_state"],
            step=jnp.asarray(meta["step"], jnp.int32),
        )
        return int(meta["step"])

    # -- main loop -----------------------------------------------------------
    def _shape_batch(self, batch):
        if self.tcfg.microbatches <= 1:
            return batch
        n = self.tcfg.microbatches

        def resh(x):
            b = x.shape[0]
            assert b % n == 0, f"batch {b} not divisible by {n} microbatches"
            return x.reshape(n, b // n, *x.shape[1:])

        return jax.tree_util.tree_map(resh, batch)

    def run(self, n_steps: int, log_every: int = 10,
            fail_at_step: Optional[int] = None) -> list[dict]:
        """fail_at_step: raise a simulated node failure (tests/drills)."""
        start = int(self.state.step)
        try:
            for i in range(start, start + n_steps):
                if fail_at_step is not None and i == fail_at_step:
                    raise RuntimeError(f"simulated node failure at step {i}")
                with span("train.batch", self.stats):
                    batch = jax.tree_util.tree_map(jnp.asarray,
                                                   next(self.data))
                t0 = time.perf_counter()
                with span("train.dispatch", self.stats):
                    self.state, metrics = self.step_fn(
                        self.state, self._shape_batch(batch))
                with span("train.fetch", self.stats):
                    metrics = {k: float(v) for k, v in metrics.items()}
                dt = time.perf_counter() - t0
                if self._ema is None:
                    self._ema = dt
                else:
                    if dt > 3.0 * self._ema and i > start + 2:
                        self.straggler_events.append((i, dt))
                    self._ema = 0.9 * self._ema + 0.1 * dt
                metrics.update(step=i, step_time_s=dt)
                self.history.append(metrics)
                for h in self.hooks:
                    h(i, metrics)
                if self.ckpt is not None and \
                        (i + 1) % self.tcfg.checkpoint_every == 0:
                    self.save(i + 1)
            if self.ckpt is not None:
                self.save(int(self.state.step))
        finally:
            # drain pending async checkpoint writes even when unwinding on
            # failure: the latest durable snapshot must hit disk before any
            # restart logic (or a drill's in-process "restart") reads it
            if self.ckpt is not None:
                self.ckpt.wait()
        return self.history

    def save(self, step: int, blocking: bool = False):
        self.ckpt.save(
            step,
            {"params": self.state.params, "opt_state": self.state.opt_state},
            extra={"step": step},
            blocking=blocking,
        )
