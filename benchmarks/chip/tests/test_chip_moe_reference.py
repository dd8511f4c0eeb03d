"""The MoE / sliding-window reference against the program's
``xla_compact`` backend at a toy size on the CPU (float32, highest
precision): logits and loss over sequences longer than the window, and
the served configuration's file against the program's architecture."""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import cell  # noqa: E402
from chipbench.weights import make_weights  # noqa: E402


def _file(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def _program_cfg(config, cfg):
    """The file's routing, window and rotary sizes on the program's
    architecture (``cell.build_model`` sets the dense sizes only)."""
    yarn = config["rope_parameters"]["full_attention"]
    e, k = config["num_experts"], config["num_experts_per_tok"]
    return cfg.with_(
        sliding_window=config["sliding_window"],
        moe=dataclasses.replace(cfg.moe, n_experts=e, top_k=k,
                                d_expert=config["moe_intermediate_size"]),
        rope_scaling=dataclasses.replace(
            cfg.rope_scaling, factor=float(yarn["factor"]),
            original_max_position=yarn["original_max_position_embeddings"],
            beta_fast=float(yarn["beta_fast"]),
            beta_slow=float(yarn["beta_slow"]),
            attention_factor=yarn["attention_factor"]))


@pytest.fixture(scope="module")
def setup():
    from repro.models import LMModel

    config = _file("tests/tiny_moe.json")
    config["sparsity"] = dict(config["sparsity"], backend="xla_compact")
    config["program"]["overrides"]["compute_dtype"] = "float32"
    _, cfg = cell.build_model(config)
    cfg = _program_cfg(config, cfg)
    # capacity for every routed pair: the forward drops no token, as
    # serving (full capacity) does not
    cfg = cfg.with_(moe=dataclasses.replace(
        cfg.moe, capacity_factor=cfg.moe.n_experts / cfg.moe.top_k))
    model = LMModel(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = make_weights(shapes, 11, is_compact=cell.is_compact)
    ref = cell.ref_module(HERE, config)
    ref.configure(config)
    cols = {n: jnp.asarray(c) for n, c in ref.layouts(params).items()}
    return model, params, ref, cols, cell.ref_arch(config), config


def test_served_file_matches_the_program_architecture():
    config = _file("configs/mellum2-12b.json")
    _, cfg = cell.build_model(config)
    assert _program_cfg(config, cfg) == cfg
    kinds = {"sliding_attention": "swa", "full_attention": "attn"}
    assert [kinds[t] for t in config["layer_types"]] == [
        cfg.layer_kind(i) for i in range(cfg.n_layers)]
    assert all(t == "sparse" for t in config["mlp_layer_types"])
    assert all(cfg.is_moe_layer(i) for i in range(cfg.n_layers))
    assert cfg.moe.n_shared == 0 and cfg.rope_scaling.layer_kinds == ("attn",)
    assert config["rope_theta"] == cfg.rope_theta == \
        config["rope_parameters"]["full_attention"]["rope_theta"] == \
        config["rope_parameters"]["sliding_attention"]["rope_theta"]


def test_every_leaf_compact_but_embedding_head_router(setup):
    _, params, *_ = setup
    leaves = jax.tree_util.tree_leaves(params, is_leaf=cell.is_compact)
    assert sum(cell.is_compact(x) for x in leaves) == 4 * 7
    j0 = params["stack"]["scan"]["j0"]
    assert j0["ffn"]["experts"]["gate"].w_data.ndim == 4   # (periods, E, M, nnz)
    assert not cell.is_compact(j0["ffn"]["router"])


@pytest.mark.parametrize("S", [24, 40])
def test_forward_matches_the_program_past_the_window(setup, S):
    model, params, ref, cols, arch, config = setup
    assert S > config["sliding_window"]
    tokens = np.random.default_rng(S).integers(0, 512, (1, S), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = model.forward(params, {"tokens": jnp.asarray(tokens)})
    got = ref.forward_logits(params, cols, jnp.asarray(tokens[0]), arch)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                               rtol=2e-4, atol=2e-4)


def test_loss_matches_the_program(setup):
    """The next-token cross-entropy (the program adds its router's
    load-balance term to the loss it trains on; the reference has none)."""
    model, params, ref, cols, arch, _ = setup
    tokens = np.random.default_rng(1).integers(0, 512, (2, 32), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        _, (want, _) = model.loss(params, {"tokens": jnp.asarray(tokens)})
    got = ref.loss(params, cols, jnp.asarray(tokens), arch)
    assert float(got) == pytest.approx(float(want), rel=1e-5)


@pytest.mark.parametrize("rows", [4, 128])
def test_routed_experts_match_every_expert_weighted(setup, rows):
    """Each expert over its routed positions only (sorted, padded to
    blocks of ``rows``) equals every expert over every position weighted
    by its routing weight, zero where a position did not pick it."""
    _, _, ref, _, _, _ = setup
    S, d, h, E = 37, 16, 8, 16
    rng = np.random.default_rng(rows)
    x = jnp.asarray(rng.normal(size=(S, d)), jnp.float32)
    dense = {"gate": jnp.asarray(rng.normal(size=(E, h, d)), jnp.float32),
             "up": jnp.asarray(rng.normal(size=(E, h, d)), jnp.float32),
             "down": jnp.asarray(rng.normal(size=(E, d, h)), jnp.float32)}
    router = jnp.asarray(rng.normal(size=(E, d)), jnp.float32)
    idx, top = ref._route(x, router, None)
    weight = jnp.zeros((S, E)).at[jnp.arange(S)[:, None], idx].set(top)
    want = sum(weight[:, e, None] * ref._swiglu(
        x, dense["gate"][e], dense["up"][e], dense["down"][e], None)
        for e in range(E))
    got = ref._experts(x, dense, router, None, rows=rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_window_and_yarn_each_matter(setup):
    """The comparison would see a wrong window or plain RoPE on the full
    layers: each moves the reference's logits well past the tolerance."""
    model, params, ref, cols, arch, config = setup
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, 512, 40),
                         jnp.int32)
    base = np.asarray(ref.forward_logits(params, cols, tokens, arch))
    plain = dict(config, rope_parameters=dict(
        config["rope_parameters"],
        full_attention=config["rope_parameters"]["sliding_attention"]))
    wide = dict(config, sliding_window=4 * config["sliding_window"])
    try:
        for other in (plain, wide):
            ref.configure(other)
            got = np.asarray(ref.forward_logits(params, cols, tokens, arch))
            assert np.abs(got - base).max() > 1e-2
    finally:
        ref.configure(config)


def test_served_gap_path_runs_the_reference_by_traced_layer(setup):
    """``check.serve_gaps`` indexes layers with a traced ``i``: tokens the
    reference itself ranks first read a gap of 0; the float8 control
    reads above it."""
    from chipbench import check

    _, params, ref, cols, arch, _ = setup
    seq = np.zeros(32, np.int32)      # causal: the zeros after p are unseen
    seq[:20] = np.random.default_rng(9).integers(0, 512, 20)
    logits = jax.jit(lambda t: ref.forward_logits(params, cols, t, arch))
    for p in range(20, 32):   # greedy under the reference, past the window
        seq[p] = np.argmax(np.asarray(logits(jnp.asarray(seq))[p - 1]))
    got = check.serve_gaps(params, ref, arch, [(seq[:20], seq[20:])], 48,
                           control="float8_e4m3fn")
    assert got["positions"] == 12
    assert got["program"] < 1e-4
    assert got["control"] > got["program"]


def test_served_tokens_are_the_references_choice(setup):
    """The program served as the cell serves it (``make_engine``
    "continuous": 8-token prefill chunks, then paged decode past the
    16-token window): at float32 every greedy token is the reference's
    first choice, so the served gap reads ~0."""
    import jax.numpy as jnp
    from chipbench import check
    from repro.serve import make_engine

    model, params, ref, cols, arch, config = setup
    eng = make_engine("continuous", model, params, page_size=4, max_slots=2,
                      max_request_len=48, prefill_chunk=8,
                      cache_dtype=jnp.float32)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (21, 13, 30)]
    rids = [eng.submit(p, 12) for p in prompts]
    out = eng.drain()
    assert eng.stats["prefill_chunks"] > len(prompts)
    seqs = [(p, np.asarray(out[r])) for p, r in zip(prompts, rids)]
    assert all(len(p) + len(g) > config["sliding_window"] for p, g in seqs)
    got = check.serve_gaps(params, ref, arch, seqs, 48)
    assert got["positions"] == sum(len(g) for _, g in seqs)
    assert got["program"] < 1e-3
