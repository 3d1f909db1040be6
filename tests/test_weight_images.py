"""Decode weight images: one per distinct weight-width row of the profile
table, held as int8 carriers where every image quantizes a site at <= 8 bits.

The carriers must be exactly the fake-quant values the decode loop would
otherwise compute in-loop from the float masters, so serving tokens do not
change when the images replace the in-loop quantization.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.profiles import Profile, paper_profiles, profile_table
from repro.core.quantizers import QTensor, dequantize, fake_quant_dynamic
from repro.models import transformer as T
from repro.models.layers import SIGNED_SYM


@pytest.fixture(scope="module")
def parts():
    cfg = get_smoke("granite-3-2b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    names = T.quant_layer_names(cfg)
    return cfg, params, names


def _leaves_with_path(tree):
    return jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, QTensor))[0]


def test_one_image_per_weight_width_row(parts):
    """The six paper profiles use two weight widths (W8, W4): two images,
    every site an int8 carrier, profiles mapped by their weight row."""
    cfg, params, names = parts
    profs = paper_profiles(names, inner_layers=[])
    pq = T.prequant_decode_weights(params, cfg, profile_table(profs, names))
    image_of = np.asarray(pq["image_of"])
    w_of = [p.w_bits(names[0]) for p in profs]
    assert len(set(image_of.tolist())) == 2
    for i in range(len(profs)):
        for j in range(len(profs)):
            assert (image_of[i] == image_of[j]) == (w_of[i] == w_of[j])
    leaves = _leaves_with_path(pq["images"])
    assert leaves and all(isinstance(x, QTensor) for _, x in leaves)
    for _, qt in leaves:
        assert qt.data.dtype == jnp.int8 and qt.data.shape[0] == 2


def test_wide_rows_keep_float_images(parts):
    """A float32 profile beside the paper family adds a third weight row;
    sites it quantizes above 8 bits keep a float image (``wfq``)."""
    cfg, params, names = parts
    profs = paper_profiles(names, inner_layers=[]) + [Profile.float32(names)]
    pq = T.prequant_decode_weights(params, cfg, profile_table(profs, names))
    assert len(set(np.asarray(pq["image_of"]).tolist())) == 3
    keys = {path[-1].key for path, _ in _leaves_with_path(pq["images"])}
    assert keys == {"wfq"}


def test_carrier_is_the_fake_quant(parts):
    """Dequantizing a carrier gives the in-loop fake-quant bit for bit, for
    every image and every layer of a stacked site."""
    cfg, params, names = parts
    profs = paper_profiles(names, inner_layers=[])
    table = profile_table(profs, names)
    pq = T.prequant_decode_weights(params, cfg, table)
    w = params["layers"]["mlp"]["w_in"]["w"]             # [L, d, 2*d_ff]
    img = pq["images"]["layers"]["mlp"]["w_in"]["wq"]
    for p in range(len(profs)):
        _, _, layer_bits = T.split_bits(cfg, table[p])
        wb = layer_bits[:, T.sites(cfg).index("mlp_in"), 1]
        i = int(pq["image_of"][p])
        for layer in range(cfg.n_layers):
            got = dequantize(jax.tree.map(lambda a: a[i, layer], img),
                             jnp.float32)
            want = fake_quant_dynamic(w[layer], wb[layer], SIGNED_SYM)
            assert np.array_equal(np.asarray(got), np.asarray(want))


def _segment_matches_in_loop_quant(cfg, params, names, kv_bits, b=3):
    """``decode_segment`` through the carrier images emits the tokens (and
    writes the caches) of the in-loop fake-quant path, with the schedule
    switching across all six profiles, i.e. across both images."""
    profs = paper_profiles(names, inner_layers=[])
    table = jnp.asarray(profile_table(profs, names))
    plen, steps = 7, 12
    prompts = np.random.default_rng(5).integers(0, cfg.vocab, (b, plen))
    logits, caches = T.prefill(params, cfg, table[0],
                               {"tokens": jnp.asarray(prompts, jnp.int32)},
                               slots=32, kv_bits=kv_bits)
    tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    pos0 = jnp.full((b,), plen, jnp.int32)
    schedule = jnp.arange(steps, dtype=jnp.int32) % len(profs)
    in_loop = {"image_of": jnp.zeros((len(profs),), jnp.int32), "images": {}}

    def run(prequant):
        return jax.jit(lambda c: T.decode_segment(
            params, cfg, table, schedule, tok0, pos0, c,
            jnp.full((b,), steps, jnp.int32), prequant=prequant))(caches)

    pq = T.prequant_decode_weights(params, cfg, table)
    assert "layers" in pq["images"]
    ys_img, ok_img, _, _, c_img = run(pq)
    ys_ref, ok_ref, _, _, c_ref = run(in_loop)
    assert np.asarray(ok_img).all() and np.asarray(ok_ref).all()
    assert np.array_equal(np.asarray(ys_img), np.asarray(ys_ref))
    for a, r in zip(jax.tree.leaves(c_img), jax.tree.leaves(c_ref)):
        assert np.array_equal(np.asarray(a), np.asarray(r))


@pytest.mark.parametrize("kv_bits", [16, 8])
def test_decode_segment_images_match_in_loop_quant(parts, kv_bits):
    cfg, params, names = parts
    _segment_matches_in_loop_quant(cfg, params, names, kv_bits)


@pytest.mark.parametrize("scan_layers", [True, False])
@pytest.mark.parametrize("arch", ["granite-3-2b", "qwen2-moe-a2.7b",
                                  "mamba2-130m", "hymba-1.5b"])
def test_decode_segment_images_match_in_loop_quant_by_family(arch,
                                                             scan_layers):
    """Every non-interleaved family (dense, moe, ssm, hybrid) grafts each
    layer's image slice in its layer loop, scanned or unrolled. Four rows:
    the MoE prefill splits its tokens into two groups."""
    cfg = dataclasses.replace(get_smoke(arch), scan_layers=scan_layers)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    _segment_matches_in_loop_quant(cfg, params, T.quant_layer_names(cfg), 16,
                                   b=4)
