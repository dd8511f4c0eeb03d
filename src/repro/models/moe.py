"""Mixture-of-Experts FFN: token-choice top-k routing with capacity.

Routing is *grouped*: tokens are reshaped to (G, T_local, D) where G is the
number of data-parallel shards (1 when no mesh is installed), and every
group routes its local tokens into its own (E, C_local, D) buffer.  The
result is a pure-pjit program whose scatter/gather indices are local to each
group, so under the production mesh the dispatch partitions cleanly:
buffers are P(dp, 'model', ...) — DP x EP — with no cross-group collectives.
(A naive global scatter forced XLA to all-reduce the full expert buffer
every layer: ~200 s/step of collectives for DeepSeek-V2 at 4k train until
this change.  A shard_map formulation hit an XLA:CPU AllReducePromotion
crash under scan+remat, so grouped-pjit it is — and it needs no manual
collectives at all.)

Expert weights support the paper's technique in two storage forms, both
sharing one RBGP4 mask across the experts of a layer (cloned-mask EP keeps
the succinct storage property: one base-graph set per layer, not per
expert):

  * **masked** (``backend="xla_masked"``, the default): dense (E, M, K)
    values under the broadcast mask — E dense masked einsums;
  * **compact** (``backend="auto"``/``"pallas"``/``"xla_compact"``):
    ``CompactWeight`` with stacked (E, M, nnz_row) values and one shared
    layout, applied through ``sparse_linear_batched`` — on the pallas
    backend that is ONE stacked-grid Pallas kernel launch per projection
    for all experts (grid ``(expert, token-tile, row-tile, k)``), with the
    gate activation fused into the kernel epilogue.
"""
from __future__ import annotations

import math
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MoEConfig
from repro.kernels import EPILOGUE_ACTS
from repro.parallel.constrain import current_mesh, shard
from repro.sparsity import (
    CompactWeight,
    MaskedWeight,
    SparsityConfig,
    make_pattern,
    sparse_linear_batched,
    storage_kind,
)
from .mlp import ACTS, GatedMLP

__all__ = ["StackedExperts", "MoELayer"]


class StackedExperts:
    """(E, ...) stacked gated-MLP expert weights, RBGP4-maskable.

    ``sparsity`` is a legacy :class:`SparsityConfig` (applied by value) or
    a :class:`SparsityPlan`: the in-projection (gate+up, cloned masks) and
    the out-projection resolve at ``{name}.experts.in`` /
    ``{name}.experts.out`` — the same paths the shape recorder reports, so
    budget-solved plans land here without model edits.  The two paths must
    resolve to one spec (per-side heterogeneous expert sparsity has no
    stacked storage).
    """

    def __init__(self, n_experts: int, d_model: int, d_expert: int,
                 sparsity, act: str = "silu", name: str = "moe"):
        self.e = n_experts
        self.d = d_model
        self.h = d_expert
        self.act = ACTS[act]
        self.act_name = act
        self.name = name
        from repro.sparsity import (SparsityPlan, record_shape,
                                    recording_active)

        path_in = f"{name}.experts.in"
        path_out = f"{name}.experts.out"
        # gate + up share the in-projection shape; counts feed the planner
        record_shape(path_in, d_expert, d_model, count=2 * n_experts)
        record_shape(path_out, d_model, d_expert, count=n_experts)
        if recording_active():
            self.sparsity = SparsityConfig()
            self.backend = "auto"
            self.storage = "dense"
            self.masked = self.compact = False
            return
        if isinstance(sparsity, SparsityPlan):
            spec_in = sparsity.resolve(path_in, d_expert, d_model)
            spec_out = sparsity.resolve(path_out, d_model, d_expert)
            if spec_in != spec_out and (spec_in.is_sparse
                                        or spec_out.is_sparse):
                raise ValueError(
                    f"StackedExperts needs one spec for both expert "
                    f"projections, but the plan resolves {path_in!r} -> "
                    f"{spec_in} and {path_out!r} -> {spec_out}; write rules "
                    f"matching both paths identically")
            sparsity = spec_in.to_config()
        self.sparsity = sparsity
        self.backend = sparsity.backend
        applies = sparsity.applies_to(d_expert, d_model) and \
            sparsity.pattern != "dense"
        if applies and sparsity.pattern != "rbgp4":
            from repro.sparsity import PATTERNS

            raise NotImplementedError(
                f"StackedExperts got sparsity pattern "
                f"{sparsity.pattern!r}, but stacked expert weights support "
                f"only 'rbgp4' (one base-graph mask shared across the "
                f"expert dim) or 'dense' (sparsity 0 / below min_dim); "
                f"other registered patterns "
                f"({sorted(p for p in PATTERNS if p not in ('rbgp4', 'dense'))}) "
                f"have no stacked storage — use a per-expert MoELayer "
                f"backend or pattern='rbgp4' instead"
            )
        # storage kind follows the configured backend's capabilities, as in
        # SparseLinear: masked = dense (E, M, K) values under the broadcast
        # mask; compact = stacked (E, M, nnz_row) CompactWeight run through
        # the batched kernels
        self.storage = storage_kind(
            sparsity.backend, has_layout=True) if applies else "dense"
        self.masked = self.storage == "masked"
        self.compact = self.storage == "compact"
        if applies:
            self.pat_in = make_pattern(sparsity, d_expert, d_model)
            self.pat_out = make_pattern(sparsity, d_model, d_expert)
        if self.masked:
            # one factor-array set per pattern, shared by gate and up (the
            # succinct-storage story: one base-graph sample per layer)
            mk = lambda pat: (jnp.asarray(pat.layout.graph_o.biadjacency),
                              jnp.asarray(pat.layout.graph_i.biadjacency))
            self._ba_in = mk(self.pat_in)
            self._ba_out = mk(self.pat_out)

    def _wrap(self, w: jax.Array, pat) -> jax.Array | MaskedWeight:
        """Wrap a stacked (E, ...) expert weight in a typed container.

        One RBGP4 mask is shared across the expert dim (cloned-mask EP);
        the container's factor leaves are typed non-trainable, so the
        optimizer and checkpoints need no key-name convention.
        """
        if not self.masked:
            return w
        ba_o, ba_i = self._ba_in if pat is self.pat_in else self._ba_out
        return MaskedWeight(
            w=w, ba_o=ba_o, ba_i=ba_i,
            group_rows=pat.layout.spec.group_rows,
            chunk_cols=pat.layout.spec.chunk_cols,
        )

    def _init_compact(self, key, pat) -> CompactWeight:
        """Stacked (E, M, nnz_row) compact values sharing one layout."""
        from repro.kernels import compact_init

        lay = pat.layout
        return CompactWeight(
            w_data=compact_init(key, lay, lead=(self.e,)), layout=lay
        )

    def init(self, key) -> dict:
        ks = jax.random.split(key, 3)
        if self.compact:
            return {
                "gate": self._init_compact(ks[0], self.pat_in),
                "up": self._init_compact(ks[1], self.pat_in),
                "down": self._init_compact(ks[2], self.pat_out),
            }
        dens = 1.0 - (self.sparsity.sparsity if self.masked else 0.0)
        s_in = (2.0 / (self.d * dens)) ** 0.5
        s_out = (2.0 / (self.h * dens)) ** 0.5
        pi = self.pat_in if self.masked else None
        po = self.pat_out if self.masked else None
        return {
            "gate": self._wrap(
                jax.random.normal(ks[0], (self.e, self.h, self.d)) * s_in, pi),
            "up": self._wrap(
                jax.random.normal(ks[1], (self.e, self.h, self.d)) * s_in, pi),
            "down": self._wrap(
                jax.random.normal(ks[2], (self.e, self.d, self.h)) * s_out, po),
        }

    def coerce(self, params: dict) -> dict:
        """Upgrade pre-registry flat-dict expert params (deprecation shim).

        The legacy layout stored raw (E, ...) arrays plus ``_ba_*`` keys;
        the factors are deterministic in the pattern, so re-wrapping from
        the instance's own patterns reproduces the same masks.
        """
        if not self.masked or isinstance(params["gate"], MaskedWeight):
            return params
        warnings.warn(
            "flat-dict StackedExperts params are deprecated; pass the "
            "MaskedWeight containers returned by init()",
            DeprecationWarning, stacklevel=3,
        )
        return {
            "gate": self._wrap(params["gate"], self.pat_in),
            "up": self._wrap(params["up"], self.pat_in),
            "down": self._wrap(params["down"], self.pat_out),
        }

    def apply(self, params, xe: jax.Array) -> jax.Array:
        """xe: (G, E, C, D) -> (G, E, C, D)."""
        if self.compact:
            return self._apply_compact(params, xe)
        dt = xe.dtype
        params = self.coerce(params)
        if self.masked:
            # expand each mask once; gate and up share m_in
            m_in = params["gate"].mask_array(dt)
            wg = params["gate"].w.astype(dt) * m_in
            wu = params["up"].w.astype(dt) * m_in
            wd = params["down"].materialize(dt)
        else:
            wg = params["gate"].astype(dt)
            wu = params["up"].astype(dt)
            wd = params["down"].astype(dt)
        h = self.act(jnp.einsum("gecd,ehd->gech", xe, wg))
        h = h * jnp.einsum("gecd,ehd->gech", xe, wu)
        h = shard(h, "dp", "tp", None, None)
        return jnp.einsum("gech,edh->gecd", h, wd)

    def _apply_compact(self, params, xe: jax.Array) -> jax.Array:
        """Batched-compact path: one stacked kernel launch per projection.

        The expert dim moves to the front ((E, G*C, D) token-major
        buffers), all three projections run through
        ``sparse_linear_batched`` (pallas: the stacked-grid kernel; the
        gate activation is fused into its epilogue), and the result is
        reshaped back to the router's (G, E, C, D) buffer layout.
        """
        gn, e, cc, d = xe.shape
        x2 = jnp.moveaxis(xe, 1, 0).reshape(e, gn * cc, d)
        fuse = self.act_name if self.act_name in EPILOGUE_ACTS else None
        be = self.backend
        g = sparse_linear_batched(params["gate"], x2, backend=be, fuse=fuse)
        if fuse is None:
            g = self.act(g)
        h = g * sparse_linear_batched(params["up"], x2, backend=be)
        h = shard(h, "tp", None, None)  # expert dim on the EP axis
        y = sparse_linear_batched(params["down"], h, backend=be)
        return jnp.moveaxis(y.reshape(e, gn, cc, d), 0, 1)


class MoELayer:
    """Routed experts (+ optional shared experts) replacing the MLP."""

    def __init__(self, d_model: int, moe: MoEConfig, sparsity,
                 act: str = "silu", name: str = "moe"):
        self.d = d_model
        self.moe = moe
        self.experts = StackedExperts(
            moe.n_experts, d_model, moe.d_expert, sparsity, act, name=name
        )
        self.shared: Optional[GatedMLP] = None
        if moe.n_shared:
            self.shared = GatedMLP(
                d_model, moe.d_expert * moe.n_shared, sparsity, act,
                name=f"{name}.shared",
            )

    def init(self, key) -> dict:
        ks = jax.random.split(key, 3)
        p = {
            "router": jax.random.normal(ks[0], (self.moe.n_experts, self.d))
            * (self.d ** -0.5),
            "experts": self.experts.init(ks[1]),
        }
        if self.shared is not None:
            p["shared"] = self.shared.init(ks[2])
        return p

    def capacity(self, tokens: int, full_capacity: bool) -> int:
        """Rows C of each expert's buffer for a routing group of
        ``tokens`` tokens: all of them at full capacity, else the top-k
        share scaled by ``capacity_factor``."""
        if full_capacity:
            return tokens
        moe = self.moe
        return max(int(math.ceil(tokens * moe.top_k / moe.n_experts
                                 * moe.capacity_factor)), 1)

    def dispatch_rows(self, tokens: int,
                      full_capacity: bool) -> tuple[int, int]:
        """(expert-buffer rows E·C, routed (token, expert) pairs T·k) of
        one call over ``tokens`` tokens in one routing group: the work the
        experts compute against the work routing asked for."""
        return (self.moe.n_experts * self.capacity(tokens, full_capacity),
                tokens * self.moe.top_k)

    def _n_groups(self, batch_dim: int) -> int:
        mesh = current_mesh()
        if mesh is None:
            return 1
        dp = [a for a in mesh.axis_names if a in ("pod", "data")]
        n = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
        return n if n > 0 and batch_dim % n == 0 else 1

    def apply(
        self, params, x: jax.Array, *, full_capacity: bool = False
    ) -> tuple[jax.Array, jax.Array]:
        """x: (B, S, D) -> (y, aux_loss).

        full_capacity=True (serving) sizes expert buffers so no token is
        ever dropped — decode must be deterministic and batch-size
        independent; capacity-based dropping is a training-only trade.

        With a production mesh installed this runs the *manual* EP path
        (shard_map over every axis): tokens are dp-sharded and replicated
        across the model axis, each model rank owns E/n_model experts
        (zero-communication dispatch: each rank just keeps its experts'
        tokens), expert weights are FSDP-gathered on use, and the combine
        is one bf16-sized psum of (T_local, D) per layer — the cheapest
        communication pattern for capacity-based MoE.  The pure-pjit
        fallback (no mesh: tests/CPU examples) routes identically with
        G = 1.
        """
        mesh = current_mesh()
        # the manual shard_map path materializes masked weights; compact
        # storage runs the batched kernel under the pure-pjit formulation
        if mesh is not None and "model" in mesh.axis_names \
                and not self.experts.compact:
            dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
            ndp = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
            T = x.shape[0] * x.shape[1]
            if dp and T % ndp == 0:
                y, aux = self._route_manual(params, x, mesh, dp, full_capacity)
                if self.shared is not None:
                    y = y + self.shared.apply(params["shared"], x)
                return y, aux
        return self._route_pjit(params, x, full_capacity)

    def _route_manual(self, params, x, mesh, dp, full_capacity):
        """shard_map EP x DP x FSDP routing (see class docstring).

        f32 at the shard_map boundary: bf16 operands to the manual region
        trip an XLA:CPU AllReducePromotion crash (bisected; TPU builds run
        this in bf16 — recorded in DESIGN.md as a CPU-only workaround).
        """
        from jax.sharding import PartitionSpec as P

        moe = self.moe
        B, S, D = x.shape
        T = B * S
        E, K = moe.n_experts, moe.top_k
        ndp = int(np.prod([mesh.shape[a] for a in dp]))
        nmp = mesh.shape["model"]
        TL = T // ndp
        C = self.capacity(TL, full_capacity)
        epm = -(-E // nmp)          # experts per model rank
        Ep = epm * nmp              # padded expert count

        ex = self.experts.coerce(params["experts"])
        f32 = jnp.float32

        def raw(leaf):
            return leaf.w if isinstance(leaf, MaskedWeight) else leaf

        def pad_e(w):
            return jnp.pad(w.astype(f32), ((0, Ep - E),) + ((0, 0),) * (w.ndim - 1))

        wg, wu, wd = pad_e(raw(ex["gate"])), pad_e(raw(ex["up"])), \
            pad_e(raw(ex["down"]))
        if self.experts.masked:
            m_in = ex["gate"].mask_array(f32)
            m_out = ex["down"].mask_array(f32)
        else:
            m_in = m_out = jnp.ones((), f32)
        router = params["router"].astype(f32)
        act = self.experts.act

        def body(router, wg, wu, wd, m_in, m_out, xl):
            # xl: (TL, D) — this dp rank's tokens, replicated over 'model'
            rank = jax.lax.axis_index("model")
            logits = xl @ router.T                      # (TL, E)
            probs = jax.nn.softmax(logits, axis=-1)
            gates, idx = jax.lax.top_k(probs, K)
            gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)
            e_flat = idx.reshape(-1)                    # (TL*K,)
            onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)
            pos = jnp.cumsum(onehot, axis=0) - 1
            pos_in_e = jnp.take_along_axis(pos, e_flat[:, None], 1)[:, 0]
            keep = pos_in_e < C
            # dispatch: keep only this rank's experts — no communication
            e_rel = e_flat - rank * epm
            local = keep & (e_rel >= 0) & (e_rel < epm)
            safe_e = jnp.where(local, e_rel, 0)
            safe_p = jnp.where(local, pos_in_e, 0)
            tok = jnp.repeat(jnp.arange(TL), K)
            contrib = jnp.where(local[:, None], xl[tok], 0)
            buf = jnp.zeros((epm, C, D), f32).at[safe_e, safe_p].add(contrib)
            # FSDP: in_specs already left this rank its (epm, ...) expert
            # slice with the d axis sharded over dp — gather d on use
            gather = lambda w, ax: jax.lax.all_gather(w, dp, axis=ax, tiled=True)
            wg_l = gather(wg, 2)   # (epm, h, d)
            wu_l = gather(wu, 2)
            wd_l = gather(wd, 1)   # (epm, d, h)
            h = act(jnp.einsum("ecd,ehd->ech", buf, wg_l * m_in))
            h = h * jnp.einsum("ecd,ehd->ech", buf, wu_l * m_in)
            out = jnp.einsum("ech,edh->ecd", h, wd_l * m_out)  # (epm, C, D)
            # combine: sum over K locally, then one psum over 'model'
            got = jnp.where(local[:, None], out[safe_e, safe_p], 0)
            y = (got.reshape(TL, K, D) * gates[..., None]).sum(axis=1)
            y = jax.lax.psum(y, "model")
            # aux loss (identical on every model rank)
            frac_tok = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=f32), 0)
            aux = E * jnp.sum(frac_tok * jnp.mean(probs, 0)) * moe.aux_loss_coef
            return y, aux.reshape(1)

        wspec_in = P("model", None, dp)   # (E, h, d): E on model, d FSDP
        wspec_out = P("model", dp, None)  # (E, d, h)
        y, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), wspec_in, wspec_in, wspec_out, P(), P(),
                      P(dp)),
            out_specs=(P(dp), P(dp)), check_vma=False,
        )(router, wg, wu, wd, m_in, m_out,
          x.reshape(T, D).astype(f32))
        return y.reshape(B, S, D).astype(x.dtype), jnp.mean(aux)

    def _route_pjit(
        self, params, x: jax.Array, full_capacity: bool
    ) -> tuple[jax.Array, jax.Array]:
        moe = self.moe
        B, S, D = x.shape
        T = B * S
        E, K = moe.n_experts, moe.top_k
        G = self._n_groups(B)
        TL = T // G  # tokens per routing group
        xg = shard(x.reshape(G, TL, D), "dp", None, None)
        # named scopes mark the four phases in the ops' HLO ``op_name``
        # metadata (the stacked kernels inside ``moe.experts`` keep their
        # ``rbgp4.*`` names)
        with jax.named_scope("moe.route"):
            # router in f32 (tiny, replicated)
            logits = jnp.einsum(
                "gtd,ed->gte", xg.astype(jnp.float32),
                params["router"].astype(jnp.float32),
            )
            probs = jax.nn.softmax(logits, axis=-1)  # (G, TL, E)
            gates, idx = jax.lax.top_k(probs, K)  # (G, TL, K)
            gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)

            # per-group capacity + position-in-expert (cumsum over local
            # slots)
            C = self.capacity(TL, full_capacity)
            e_flat = idx.reshape(G, TL * K)
            onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)  # (G, TL*K, E)
            pos = jnp.cumsum(onehot, axis=1) - 1
            pos_in_e = jnp.take_along_axis(pos, e_flat[..., None],
                                           axis=2)[..., 0]
            keep = pos_in_e < C

            # load-balance aux loss (Switch-style), averaged over groups
            frac_tokens = jnp.mean(
                jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32), axis=(0, 1)
            )
            frac_probs = jnp.mean(probs, axis=(0, 1))
            aux = E * jnp.sum(frac_tokens * frac_probs) * moe.aux_loss_coef

        with jax.named_scope("moe.dispatch"):
            # scatter tokens into (G, E, C, D): indices local to each group
            gidx = jnp.broadcast_to(jnp.arange(G)[:, None], (G, TL * K))
            tok = jnp.broadcast_to(
                jnp.repeat(jnp.arange(TL), K)[None], (G, TL * K)
            )
            safe_e = jnp.where(keep, e_flat, 0)
            safe_p = jnp.where(keep, pos_in_e, 0)
            contrib = jnp.where(
                keep[..., None],
                jnp.take_along_axis(xg, tok[..., None], axis=1), 0
            ).astype(x.dtype)
            buf = jnp.zeros((G, E, C, D), x.dtype).at[
                gidx, safe_e, safe_p].add(contrib)
            buf = shard(buf, "dp", "tp", None, None)  # DP x EP

        with jax.named_scope("moe.experts"):
            out_buf = self.experts.apply(params["experts"], buf)  # (G, E, C, D)
            out_buf = shard(out_buf, "dp", "tp", None, None)

        with jax.named_scope("moe.combine"):
            # gather back, weighted by gates
            got = out_buf[gidx, safe_e, safe_p]  # (G, TL*K, D)
            got = jnp.where(keep[..., None], got, 0)
            y = (got.reshape(G, TL, K, D)
                 * gates[..., None].astype(x.dtype)).sum(axis=2)
            y = shard(y, "dp", None, None).reshape(B, S, D)

        if self.shared is not None:
            y = y + self.shared.apply(params["shared"], x)
        return y, aux
