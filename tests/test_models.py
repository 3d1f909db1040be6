"""Per-architecture smoke tests (reduced same-family configs, brief §ARCH):
one forward/train step on CPU asserting output shapes + finiteness, and one
decode step for causal archs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke, SHAPES, shape_applicable
from repro.core import AdaptiveEngine, QuantIndex
from repro.core.profiles import paper_profiles
from repro.models import transformer as T


def _batch(cfg, key, B=2, S=32):
    if cfg.frontend == "audio":
        return {"features": jax.random.normal(key, (B, S, cfg.feature_dim)),
                "labels": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if cfg.frontend == "vision":
        return {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab),
                "patch_embeds": jax.random.normal(key, (B, cfg.n_patches,
                                                        cfg.d_model)),
                "labels": jnp.where(jnp.arange(S)[None] < cfg.n_patches, -100,
                                    jax.random.randint(key, (B, S), 0,
                                                       cfg.vocab))}
    return {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab),
            "labels": jax.random.randint(key, (B, S), 0, cfg.vocab)}


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_train_and_decode(arch):
    cfg = get_smoke(arch)
    key = jax.random.PRNGKey(0)
    params = T.init_params(cfg, key)
    names = T.quant_layer_names(cfg)
    profs = paper_profiles(names,
                           inner_layers=[n for n in names if n.startswith("L1.")])
    eng = AdaptiveEngine(tuple(profs), QuantIndex(names),
                         lambda p, br, b: T.train_loss(p, cfg, br, b))
    batch = _batch(cfg, key)
    loss, metrics = jax.jit(eng)(params, 2, batch)
    assert np.isfinite(float(loss))
    assert np.isfinite(float(metrics["xent"]))
    # gradient step finiteness
    g = jax.grad(lambda p: eng(p, 2, batch)[0])(params)
    gnorm = sum(float(jnp.sum(jnp.square(l))) for l in jax.tree_util.tree_leaves(g))
    assert np.isfinite(gnorm) and gnorm > 0
    if cfg.causal:
        caches = T.init_caches(cfg, 2, 16, kv_bits=16)
        br = eng.bits_row(2)
        logits, new_caches = T.decode_step(
            params, cfg, br, jnp.zeros((2, 1), jnp.int32),
            jnp.zeros((2,), jnp.int32), caches)
        assert logits.shape == (2, cfg.vocab)
        assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_brief(arch):
    """The full configs carry the exact published hyperparameters."""
    cfg = get_config(arch)
    expected = {
        "deepseek-moe-16b": (28, 2048, 16, 16, 1408, 102400),
        "qwen2-moe-a2.7b": (24, 2048, 16, 16, 1408, 151936),
        "qwen2-72b": (80, 8192, 64, 8, 29568, 152064),
        "glm4-9b": (40, 4096, 32, 2, 13696, 151552),
        "granite-3-2b": (40, 2048, 32, 8, 8192, 49155),
        "granite-4.0-h-micro": (40, 2048, 32, 8, 8192, 100352),
        "qwen1.5-110b": (80, 8192, 64, 8, 49152, 152064),
        "qwen2-vl-2b": (28, 1536, 12, 2, 8960, 151936),
        "mamba2-130m": (24, 768, 24, 24, 0, 50280),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
        "hubert-xlarge": (48, 1280, 16, 16, 5120, 504),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.d_ff, cfg.vocab)
    assert got == expected
    if arch == "deepseek-moe-16b":
        assert (cfg.moe.n_routed, cfg.moe.top_k, cfg.moe.n_shared) == (64, 6, 2)
    if arch == "qwen2-moe-a2.7b":
        assert (cfg.moe.n_routed, cfg.moe.top_k, cfg.moe.n_shared) == (60, 4, 4)
    if arch == "mamba2-130m":
        assert cfg.ssm.d_state == 128
    if arch == "hymba-1.5b":
        assert cfg.ssm.d_state == 16 and cfg.sliding_window > 0
    if arch == "hubert-xlarge":
        assert not cfg.causal
    if arch == "granite-4.0-h-micro":
        assert [i for i, k in enumerate(cfg.layer_types)
                if k == "attention"] == [5, 15, 25, 35]
        assert (cfg.ssm.d_state, cfg.ssm.head_dim, cfg.ssm.n_groups,
                cfg.rope) == (128, 64, 1, False)


def test_shape_skip_rules():
    """Brief-mandated skips: long_500k for full-attention, decode for encoder."""
    long5 = SHAPES["long_500k"]
    dec = SHAPES["decode_32k"]
    assert shape_applicable(get_config("mamba2-130m"), long5)[0]
    assert shape_applicable(get_config("hymba-1.5b"), long5)[0]
    for a in ("qwen2-72b", "glm4-9b", "deepseek-moe-16b", "hubert-xlarge"):
        assert not shape_applicable(get_config(a), long5)[0]
    assert not shape_applicable(get_config("hubert-xlarge"), dec)[0]
    assert shape_applicable(get_config("qwen2-72b"), dec)[0]


def test_quant_layer_names_cover_all_layers():
    cfg = get_smoke("granite-3-2b")
    names = T.quant_layer_names(cfg)
    assert names[0] == "embed" and names[1] == "lm_head"
    assert len(names) == 2 + cfg.n_layers * 4  # qkv/attn_out/mlp_in/mlp_out


def test_scan_vs_unrolled_equivalence():
    """The depth-unrolled analysis variant computes the same function."""
    import dataclasses
    cfg = get_smoke("granite-3-2b")
    key = jax.random.PRNGKey(1)
    params = T.init_params(cfg, key)
    names = T.quant_layer_names(cfg)
    from repro.core.profiles import Profile, profile_table
    br = profile_table([Profile.float32(names)], names)[0]
    batch = _batch(cfg, key)
    h1, a1, _ = T.forward(params, cfg, br, batch)
    cfg2 = dataclasses.replace(cfg, scan_layers=False, unroll_inner=True)
    h2, a2, _ = T.forward(params, cfg2, br, batch)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=2e-5, atol=2e-5)


def test_swa_block_skip_matches_masked():
    """The block-skipping SWA path (§Perf) is numerically exact vs masking."""
    from repro.models.attention import gqa_attention, swa_attention
    key = jax.random.PRNGKey(11)
    B, S, H, Hkv, D, w = 2, 160, 4, 2, 16, 48
    q = jax.random.normal(key, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, D))
    ref = gqa_attention(q, k, v, causal=True, window=w, block_k=32)
    out = swa_attention(q, k, v, window=w, block_q=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_constraints_are_noop_when_disabled():
    from repro.models import pshard
    assert not pshard.enabled()
    x = jnp.ones((4, 8))
    y = pshard.constrain(x, "dp", "tp")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
