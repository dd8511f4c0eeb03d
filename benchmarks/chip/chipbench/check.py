"""The comparisons that decide ``correct``, against the plain reference.

Serving: the served tokens of a sample of finished requests, fed back
through the float32 reference (teacher forcing).  At each served
position the reading is how far the served token's reference logit lies
below the reference's best, in units of that position's logit standard
deviation; the number compared is the widest such gap.  The control puts
the reference in the program's place at a lower precision: at each of
the same positions, the gap of the token the lower precision ranks
first.

Training: the first three steps' losses, the first gradient as AdamW got
it (its first moment over ``1 - b1``), and each leaf's change after three
steps, against the reference's three AdamW steps on the same batches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["serve_gaps", "sample_records", "train_reference",
           "leaf_gap", "rel_gap"]


def sample_records(records: list[dict], seed: int, k: int) -> list[dict]:
    """``k`` finished requests drawn from the seed, the longest among them."""
    if not records:
        return []
    longest = max(records, key=lambda r: r["prompt_len"] + len(r["tokens"]))
    rest = [r for r in records if r is not longest]
    rng = np.random.default_rng([seed, 5])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


@functools.partial(jax.jit, static_argnames=("ref", "arch", "fake_quant"))
def _ref_layer(params, cols, x, cx, i, *, ref, arch, fake_quant):
    """Layer ``i`` of the reference over a batch of padded sequences
    x (B, L, d), and of the control over ``cx`` when it is given; the
    layer is densified once for both."""
    a = dict(arch)
    w = ref.layer_weights(params, i)
    dense = ref.dense_weights(w, cols, a)
    x = jax.lax.map(lambda xs: ref.layer_dense(xs, dense, w, a), x)
    if cx is not None:
        cx = jax.lax.map(
            lambda xs: ref.layer_dense(xs, dense, w, a, fake_quant), cx)
    return x, cx


@functools.partial(jax.jit, static_argnames=("ref", "arch", "fake_quant"))
def _ref_gaps(params, x, toks, cx, *, ref, arch, fake_quant):
    """Per position: the reference's gap of the served next token, and of
    the control's first choice (when ``cx`` is given)."""
    a = dict(arch)

    def one(args):
        xs, t, c = args
        lg = ref.head(xs, params, a)                         # (L, V)
        best = lg.max(-1)[:-1]
        sd = lg.std(-1)[:-1]
        served = jnp.take_along_axis(lg[:-1], t[1:, None], -1)[:, 0]
        gap = (best - served) / sd
        if c is None:
            return gap, gap
        pick = jnp.argmax(ref.head(c, params, a, fake_quant)[:-1], -1)
        cval = jnp.take_along_axis(lg[:-1], pick[:, None], -1)[:, 0]
        return gap, (best - cval) / sd

    return jax.lax.map(one, (x, toks, cx))


def serve_gaps(params, ref_module, arch: dict, seqs: list, pad_to: int,
               control: str | None = None) -> dict:
    """Reference gaps over served sequences.

    ``seqs``: [(prompt, generated)] int32 arrays.  Every sequence is padded
    to ``pad_to`` (causal attention: padding after a position cannot reach
    it), so one compiled program serves every sample.  Returns
    ``{"program": widest served gap, "control": widest control gap or
    None, "positions": positions compared}``."""
    arch_t = tuple(sorted(arch.items()))
    cols = {n: jnp.asarray(c) for n, c in ref_module.layouts(params).items()}
    B = len(seqs)
    toks = np.zeros((B, pad_to), np.int32)
    mask = np.zeros((B, pad_to - 1), bool)
    for b, (p, g) in enumerate(seqs):
        s = np.concatenate([p, g])
        toks[b, :len(s)] = s
        # position i predicts token i+1: the served tokens are i+1 in
        # [len(p), len(s))
        mask[b, len(p) - 1:len(s) - 1] = True
    toks_d = jnp.asarray(toks)
    x = jax.vmap(lambda t: ref_module.embed(t, params))(toks_d)
    cx = x if control else None
    fq = jnp.dtype(control) if control else None
    for i in range(ref_module.n_layers(params)):
        x, cx = _ref_layer(params, cols, x, cx, jnp.int32(i), ref=ref_module,
                           arch=arch_t, fake_quant=fq)
    gap, cgap = _ref_gaps(params, x, toks_d, cx, ref=ref_module, arch=arch_t,
                          fake_quant=fq)
    gap, cgap = np.asarray(gap), np.asarray(cgap)
    if not np.isfinite(gap[mask]).all():
        raise FloatingPointError("non-finite reference logits")
    return {"program": float(gap[mask].max()),
            "control": float(np.nanmax(cgap[mask])) if control else None,
            "positions": int(mask.sum())}


def rel_gap(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), 1e-30)


def leaf_gap(got: dict, want: dict, skip=()) -> tuple[float, str]:
    """Worst leaf: |norm_got - norm_want| over the larger of the leaf's
    reference norm and the median leaf's."""
    med = float(np.median([v for k, v in want.items() if k not in skip]))
    worst, name = 0.0, ""
    for k, w in want.items():
        if k in skip:
            continue
        g = abs(got[k] - w) / max(w, med)
        if g > worst:
            worst, name = g, k
    return worst, name


def train_reference(params, ref_module, arch: dict, batches: list,
                    hp: dict, fake_quant: str | None = None,
                    phase=lambda name: None) -> dict:
    """Three (or ``len(batches)``) reference AdamW steps from ``params``.

    Returns per-step losses, the first step's clipped gradient norm per
    leaf, and each leaf's change norm after the last step; leaves are
    keyed by their path in ``params``."""
    cols = {n: jnp.asarray(c) for n, c in ref_module.layouts(params).items()}
    a = dict(arch)
    fq = jnp.dtype(fake_quant) if fake_quant else None

    def loss_fn(p, cols, tokens):
        return _remat_loss(p, cols, tokens, a, fq, ref_module)

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    b1, b2, eps, wd, lr, clip = (hp["b1"], hp["b2"], hp["eps"],
                                 hp["weight_decay"], hp["lr"], hp["grad_clip"])
    leaves0, tdef = jax.tree_util.tree_flatten_with_path(params)
    names = [jax.tree_util.keystr(k) for k, _ in leaves0]
    p = [jnp.array(x, jnp.float32) for _, x in leaves0]
    del leaves0, params
    m = [jnp.zeros_like(x) for x in p]
    v = [jnp.zeros_like(x) for x in p]
    d = [jnp.zeros_like(x) for x in p]       # the change since step 0
    losses, g1 = [], None

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def adam(p, m, v, d, g, t):
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in g))
        scale = jnp.minimum(1.0, clip / (norm + 1e-9))
        g = [x * scale for x in g]
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        m = [b1 * mi + (1 - b1) * gi for mi, gi in zip(m, g)]
        v = [b2 * vi + (1 - b2) * gi * gi for vi, gi in zip(v, g)]
        u = [-lr * ((mi / bc1) / (jnp.sqrt(vi / bc2) + eps) + wd * pi)
             for pi, mi, vi in zip(p, m, v)]
        return ([pi + ui for pi, ui in zip(p, u)], m, v,
                [di + ui for di, ui in zip(d, u)],
                [jnp.sqrt(jnp.sum(x * x)) for x in g])

    for t, batch in enumerate(batches, start=1):
        loss, g = grad_fn(jax.tree_util.tree_unflatten(tdef, p), cols,
                          jnp.asarray(batch))
        p, m, v, d, gn = adam(p, m, v, d, jax.tree_util.tree_leaves(g),
                              jnp.float32(t))
        del g
        losses.append(float(loss))
        phase(f"reference step {t}")
        if g1 is None:
            g1 = dict(zip(names, map(float, gn)))
    delta = dict(zip(names, map(float, jax.device_get(
        [jnp.sqrt(jnp.sum(x * x)) for x in d]))))
    return {"losses": losses, "grad_norms": g1, "delta_norms": delta}


def _remat_loss(params, cols, tokens, arch, fq, ref):
    """The reference loss with each layer rematerialised, so the backward
    keeps one layer's dense weights at a time."""
    def one(t):
        x = ref.embed(t, params)

        @jax.checkpoint
        def body(x, i):
            w = ref.layer_weights(params, i)
            return ref.layer(x, w, cols, arch, fq), None

        x, _ = jax.lax.scan(body, x, jnp.arange(ref.n_layers(params)))
        lg = ref.head(x, params, arch, fq)[:-1]
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        return jnp.take_along_axis(lg, t[1:, None], axis=-1)[:, 0] - lse
    return -jnp.mean(jax.vmap(one)(tokens))
