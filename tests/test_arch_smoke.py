"""Per-architecture smoke tests: reduced config, one forward + train step.

Exercises the exact code paths of each assigned arch (layer pattern, MoE
cadence, MLA, mamba, rwkv, frontends) at CPU-friendly sizes, asserting
output shapes and absence of NaNs.  The FULL configs are exercised only via
the dry-run (launch/dryrun.py, abstract shapes).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, input_specs, list_archs, reduce_config, shape_cells
from repro.models import LMModel
from repro.utils import merge_trees, split_trainable

LM_ARCHS = list_archs(lm_only=True)


def _batch(cfg, B=2, S=16, key=0):
    shape = (B, S, cfg.n_codebooks) if cfg.n_codebooks > 1 else (B, S)
    batch = {
        "tokens": jax.random.randint(
            jax.random.PRNGKey(key), shape, 0, cfg.vocab_size
        )
    }
    if cfg.frontend == "vision":
        batch["patch_embeds"] = jax.random.normal(
            jax.random.PRNGKey(key + 1), (B, cfg.n_patches, cfg.d_model)
        )
    return batch


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_reduced_forward_and_train_step(arch):
    cfg = reduce_config(get_config(arch))
    model = LMModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)

    logits, aux = model.forward(params, batch, train=True)
    if cfg.n_codebooks > 1:
        assert logits.shape == (2, 16, cfg.n_codebooks, cfg.vocab_size)
    else:
        assert logits.shape == (2, 16, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any()), f"{arch}: NaN logits"

    # one SGD step decreases nothing catastrophically (finite grads)
    train, static = split_trainable(params)

    @jax.jit
    def step(train):
        def loss_fn(t):
            return model.loss(merge_trees(t, static), batch)[0]
        loss, g = jax.value_and_grad(loss_fn)(train)
        new_train = jax.tree_util.tree_map(
            lambda p, gg: None if p is None else p - 1e-2 * gg,
            train, g, is_leaf=lambda x: x is None,
        )
        return loss, new_train

    loss0, train1 = step(train)
    loss1, _ = step(train1)
    assert np.isfinite(float(loss0)) and np.isfinite(float(loss1)), arch
    assert float(loss1) < float(loss0) + 0.5, f"{arch}: loss exploded"


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_reduced_decode_step(arch):
    cfg = reduce_config(get_config(arch))
    model = LMModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, B=2, S=8)
    cache = model.init_cache(2, 32, jnp.float32)
    lg, cache = model.prefill(params, batch, cache)
    tok = batch["tokens"][:, :1]
    lg2, cache = model.decode_step(params, tok, cache, jnp.int32(8))
    want = (2, cfg.n_codebooks, cfg.vocab_size) if cfg.n_codebooks > 1 \
        else (2, cfg.vocab_size)
    assert lg2.shape == want
    assert not bool(jnp.isnan(lg2).any()), arch


def test_all_archs_present():
    assert len(LM_ARCHS) == 11
    assert len(list_archs()) == 13  # + the paper's two vision models


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_shape_cells_assignment(arch):
    cfg = get_config(arch)
    cells = shape_cells(cfg)
    assert [c[0].name for c in cells] == [
        "train_4k", "prefill_32k", "decode_32k", "long_500k"
    ]
    skips = {c[0].name: c[1] for c in cells}
    long_ok = arch in ("rwkv6-7b", "jamba-1.5-large-398b", "gemma3-4b")
    assert (skips["long_500k"] is None) == long_ok
    assert all(skips[n] is None for n in ("train_4k", "prefill_32k", "decode_32k"))


@pytest.mark.parametrize("arch", ["gemma-7b", "deepseek-v2-236b", "musicgen-medium",
                                  "pixtral-12b", "rwkv6-7b"])
def test_input_specs_abstract(arch):
    cfg = get_config(arch)
    for shp, skip in shape_cells(cfg):
        if skip:
            continue
        specs = input_specs(cfg, shp)
        leaves = jax.tree_util.tree_leaves(specs)
        assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
        if shp.kind in ("train", "prefill"):
            t = specs["batch"]["tokens"]
            assert t.shape[:2] == (shp.global_batch, shp.seq_len)
        else:
            assert specs["tokens_new"].shape[1] == 1
            assert specs["index"].shape == ()


def test_full_config_param_counts():
    """Sanity: abstract param counts are in the right ballpark."""
    expect = {
        "gemma-7b": (7.7e9, 9.5e9),
        "tinyllama-1.1b": (1.0e9, 1.2e9),
        "deepseek-7b": (6.5e9, 7.5e9),
        "deepseek-v2-236b": (2.1e11, 2.6e11),
        "qwen2-moe-a2.7b": (1.2e10, 1.6e10),
        "rwkv6-7b": (7.0e9, 8.5e9),
        "jamba-1.5-large-398b": (3.5e11, 4.4e11),
        "musicgen-medium": (1.3e9, 2.0e9),
        "mellum2-12b-a2.5b": (1.15e10, 1.3e10),
    }
    for arch, (lo, hi) in expect.items():
        cfg = get_config(arch)
        n = LMModel(cfg).n_params()
        assert lo <= n <= hi, f"{arch}: {n:.3e} not in [{lo:.1e}, {hi:.1e}]"
