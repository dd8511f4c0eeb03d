"""Mesh-sharded serving engines: TP×EP continuous batching + disaggregation.

Two engines on top of the PR 3 continuous-batching loop:

  * :class:`ShardedContinuousEngine` — the same submit/step/drain loop, but
    every jitted program (prefill, chunk prefill, paged decode) runs SPMD
    over a ``('data', 'model')`` mesh.  Weights are laid out by
    ``parallel.sharding.param_sharding_tree`` (column/row-parallel
    projections, experts over 'model' = EP), the page pools by
    ``page_pool_specs`` (heads over 'model' = TP, blocks replicated), and
    the model's internal ``shard()`` constraints activate because
    ``activation_mesh(mesh)`` is entered *inside* the traced function —
    a context entered outside ``jax.jit`` would be gone by the time the
    cached program re-runs.
  * :class:`DisaggregatedEngine` — prefill and decode as separate roles on
    separate (sub)meshes.  The decode role is a ShardedContinuousEngine;
    the prefill role owns its own param copy + compiled programs on
    ``prefill_mesh``.  A finished prefill hands its KV off explicitly:
    pack the contiguous cache into page-shaped leaves, ``device_put`` them
    to the decode pools' shardings (the only cross-role transfer), then
    splice the request's blocks into the decode-side block table.  Long
    prompts therefore never occupy the decode mesh at all.

Parity: both engines must emit greedy tokens identical to the PR 3
``run_sequential`` oracle (tests/test_serve_sharded.py runs this on a
forced 4-device CPU mesh) — with the oracle handed the *engine's own
sharded params* (``eng.params``).  Sharding a contraction (row-parallel
wo/down, FSDP'd reduce dims, the EP expert-sum) turns that matmul into
partial-products + psum; the ulp-level reduction reorder is then
chaotically amplified through the depth of the network, so comparing a
sharded run against a replicated run is meaningless even at the token
level (a random-init test model has near-tied logits everywhere).  What
IS exact — and what the tests pin — is that the serving machinery itself
(paging, batching, chunking, role handoff) never changes bits: every op
with identically-sharded operands partitions identically in every
program, so engine and oracle agree token-for-token when they share the
weight layout.  For the same reason ``constrain_activations`` defaults to
False here: extra ``with_sharding_constraint`` points would make the
engine's programs partition differently from the oracle's; enable it on
real meshes where throughput matters more than replaying the oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.tp import use_kernel_mesh
from repro.parallel.constrain import activation_mesh
from repro.parallel.sharding import param_sharding_tree

from .cache import PagedKVCache
from .engine import ContinuousEngine

__all__ = ["ShardedContinuousEngine", "DisaggregatedEngine"]


def _role_fns(model, mesh, constrain: bool):
    """Jitted (prefill, chunk, decode) programs for one mesh role.

    Every program is traced inside ``use_kernel_mesh(mesh)``.  With
    ``constrain``, ``activation_mesh`` also wraps the model call *inside*
    the traced function so ``current_mesh()`` checks in the layers resolve
    at trace time (a context entered outside ``jax.jit`` is gone by the
    time the cached program re-runs); the jit cache then bakes the
    constraints in.
    """
    import contextlib

    @contextlib.contextmanager
    def ctx():
        # Pallas-backed layers find the mesh at trace time and run their
        # kernels under shard_map (kernels/tp.py)
        with use_kernel_mesh(mesh):
            if constrain:
                with activation_mesh(mesh):
                    yield
            else:
                yield

    def prefill(params, batch, cache):
        with ctx():
            return model.prefill(params, batch, cache)

    def chunk(params, batch, cache, index, n_valid):
        with ctx():
            return model.prefill_chunk(params, batch, cache, index, n_valid)

    def decode(params, tokens, pools, block_tables, positions):
        with ctx():
            return model.decode_step_paged(params, tokens, pools,
                                           block_tables, positions)

    return (jax.jit(prefill),
            jax.jit(chunk, donate_argnums=(2,)),
            jax.jit(decode, donate_argnums=(2,)))


class ShardedContinuousEngine(ContinuousEngine):
    """Continuous batching with params/pools sharded over ``mesh``.

    Same knobs as :class:`ContinuousEngine` plus the mesh.  Host-side
    bookkeeping (scheduler, allocator, block tables) is untouched — block
    tables and positions enter the jit replicated, only tensors shard.

    That includes preemption: victim selection under ``reserve="prompt"``
    pool pressure is the inherited host-side ``_pick_victim`` — ``min``
    over live requests keyed ``(priority, -arrival_step, -rid)`` — and
    never consults device state, so a TP x EP engine preempts *the same
    victims at the same clocks* regardless of how the mesh is carved up
    (``preempt_log`` traces are compared across mesh shapes in
    tests/test_serve_sharded.py).
    """

    kind = "sharded"

    def __init__(self, model, params, mesh, *,
                 constrain_activations: bool = False, **kw):
        self.mesh = mesh
        self.constrain_activations = constrain_activations
        params = jax.device_put(params, param_sharding_tree(params, mesh))
        super().__init__(model, params, **kw)

    def _make_kv(self, n_blocks: int) -> PagedKVCache:
        return PagedKVCache(self.model, n_blocks, self.page,
                            self.cache_dtype, mesh=self.mesh)

    def _jit_fns(self) -> None:
        self._prefill, self._chunk, self._decode = _role_fns(
            self.model, self.mesh, self.constrain_activations
        )


class DisaggregatedEngine(ShardedContinuousEngine):
    """Prefill/decode disaggregation with explicit KV-page handoff.

    ``decode_mesh`` hosts the decode role (weights, page pools, the batched
    decode step); ``prefill_mesh`` hosts a second weight copy and runs
    every prefill — single-shot or chunked — on its own devices.  Handoff
    lifecycle per request:

      1. prefill role fills a contiguous temp cache (chunk by chunk if
         ``prefill_chunk > 0``) and emits the first-token logits;
      2. the cache is packed into page-shaped leaves and ``device_put`` to
         the decode pools' shardings (:meth:`_handoff` — the one transfer);
      3. the pages are scattered into the decode pools and the request's
         blocks spliced into the decode block table; from then on the
         request is a plain decode row.

    The correctness contract is unchanged: the handoff moves bits, it
    never recomputes them, so greedy parity with the single-role engines
    (and the sequential oracle) holds token-for-token.
    """

    kind = "disagg"

    def __init__(self, model, params, decode_mesh, prefill_mesh, **kw):
        self.prefill_mesh = prefill_mesh
        super().__init__(model, params, decode_mesh, **kw)
        self.prefill_params = jax.device_put(
            params, param_sharding_tree(params, prefill_mesh)
        )
        self.stats.update(handoffs=0)

    def _jit_fns(self) -> None:
        _, _, self._decode = _role_fns(self.model, self.mesh,
                                       self.constrain_activations)
        self._prefill, self._chunk, _ = _role_fns(
            self.model, self.prefill_mesh, self.constrain_activations
        )

    def _handoff(self, paged):
        """device_put the packed pages from the prefill role onto the
        decode pools' layout (TP over heads, blocks replicated)."""
        self.stats["handoffs"] += 1
        self._obs.instant("kv_handoff", step=self._clock)
        if self.kv.shardings is None:
            return paged
        return jax.tree_util.tree_map(jax.device_put, paged,
                                      self.kv.shardings)

    def _localize(self, cache):
        """Reverse handoff for prefix sharing: a gathered prefix is read
        from the *decode-role* pools, but the suffix chunk program runs on
        the prefill mesh.  Round-trip through host memory so the leaves
        arrive uncommitted and the prefill-mesh program places them freely
        — bits move, nothing is recomputed, so the shared-prefill parity
        argument is unchanged."""
        return jax.tree_util.tree_map(
            lambda leaf: jnp.asarray(np.asarray(leaf)), cache
        )
