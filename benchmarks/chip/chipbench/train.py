"""Training runner: the program's ``Trainer`` driven step by step.

Set-up builds one ``Trainer`` (its jitted step and its state) and drives
it through its first three steps, reading what the comparison needs as it
goes: each step's loss, the first gradient as AdamW got it, and each
leaf's change after the third step.  The same object then runs the
window, on the same feed.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

__all__ = ["TrainRun", "leaf_norms"]

CHECK_STEPS = 3


def leaf_norms(tree) -> dict:
    """Per-leaf L2 norms, keyed by the leaf's path."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    vals = jax.device_get([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                           for _, l in leaves])
    return {jax.tree_util.keystr(p): float(v)
            for (p, _), v in zip(leaves, vals)}


class TrainRun:
    def __init__(self, trainer, b1: float, tokens_per_step: int, annotate=None):
        self.trainer = trainer
        self.b1 = b1
        self.tokens_per_step = tokens_per_step
        self.annotate = annotate
        self.losses: list[float] = []
        self.grad_norms: dict = {}
        self.delta_norms: dict = {}
        self.steps: list[tuple[float, float]] = []

    def _one(self) -> dict:
        if self.annotate is not None:
            with self.annotate("trainer.step"):
                return self.trainer.run(1)[-1]
        return self.trainer.run(1)[-1]

    def check_steps(self, params0_fn, phase=lambda name: None) -> None:
        """The first ``CHECK_STEPS`` steps, through the window's own call.

        ``params0_fn()`` rebuilds the initial weights from the seed (the
        step donates its state, so the program's copy is gone);
        ``phase(name)`` reports set-up progress."""
        for i in range(CHECK_STEPS):
            h = self._one()
            phase(f"checked step {i + 1}")
            self.losses.append(float(h["loss"]))
            if i == 0:
                m = self.trainer.state.opt_state["m"]
                self.grad_norms = {k: v / (1.0 - self.b1)
                                   for k, v in leaf_norms(m).items()}
        p0 = params0_fn()
        p3 = self.trainer.state.params
        leaves0 = dict((jax.tree_util.keystr(p), l) for p, l in
                       jax.tree_util.tree_flatten_with_path(p0)[0])
        diffs = {}
        for path, l in jax.tree_util.tree_flatten_with_path(p3)[0]:
            k = jax.tree_util.keystr(path)
            diffs[k] = jnp.sqrt(jnp.sum(jnp.square(
                l.astype(jnp.float32) - leaves0[k].astype(jnp.float32))))
        self.delta_norms = {k: float(v) for k, v in
                            jax.device_get(diffs).items()}
        del p0, leaves0

    def run_for(self, seconds: float) -> tuple[float, float, int]:
        """Whole steps until ``seconds`` have passed.  Returns the start of
        the first step, the end of the last (on ``block_until_ready`` of
        the train state) and the number of steps."""
        jax.block_until_ready(self.trainer.state.params)
        t0 = time.perf_counter()
        end = t0 + seconds
        n = 0
        while time.perf_counter() < end:
            s = time.perf_counter()
            self._one()
            self.steps.append((s, time.perf_counter()))
            n += 1
        jax.block_until_ready(self.trainer.state.params)
        return t0, time.perf_counter(), n
