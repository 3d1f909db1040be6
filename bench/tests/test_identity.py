"""``arch/dense_decoder.py`` makes bitwise the weights and the reference
logits the harness made before architectures became modules of their own.

The digests below were recorded on the CPU (jax 0.9.0) at commit d06e231,
with ``bench.weights.make`` and ``bench.reference.make_forward`` as they
stood there: sha256 of the bytes of every weight leaf (bf16 matrices) at
``tiny.py``'s model, without and with QKV bias, and of the reference logits
of a fixed 24-token sequence at the configured A16-W8 and at the control's
A8-W8.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec
from bench.tests import tiny

SEED = 2**33 + 12345
TOKENS = np.arange(24, dtype=np.int32) * 37 % 512

SHARED = {
    "['layers']['attn_out']['w']":
        "52653d822c8b8ff7bd9067305ad6da77dbb15d8dc1b41e48cf9be53a1753dc1d",
    "['layers']['mlp']['w_in']['w']":
        "6f677e56e82e6739604bd501b2a9c00fc3ff53688eec11c62b9d636ffdc267ee",
    "['layers']['mlp']['w_out']['w']":
        "213f11ea4e5b8a9acf6313bd9c0c651df8fda4913480a37dc819ff5e989b2cab",
    "['layers']['norm_attn']['g']":
        "ac026ccab0601c18c20ba6490848935743f2877a36945784efb2790d8a9b28e6",
    "['layers']['norm_mlp']['g']":
        "543c9bc00e7252162c7177836759b2fe7c023b540654f945698b6247a8fe1746",
    "['layers']['qkv']['w']":
        "b9a5d49cd0f65b314bfec3080255468ee63cb8aee66e464d43415321128cc60c",
}
CASES = {
    "plain": ({}, SHARED | {
        "['embed']['w']":
            "026e5966037970b40b1247f4c5434f73fbd69c702758c139a149f453d366e2eb",
        "['norm_f']['g']":
            "8d6bd62925416d7de79acd37690c24a885ed2cafdfaf470034b67f6ad092a69d",
    }, {
        16: "55209df701381e75879fba0f8539e6e4c359548f6a9cd0ff35d3c80d474423a9",
        8: "ca89c64a3993800ee125f60fd1fca6a40f8b1b944e1bd1c88af8e6d9f333371c",
    }),
    "qkv_bias": ({"attention_bias": True}, SHARED | {
        "['embed']['w']":
            "d7aff75407e59a546eacc7ed3044a47f2776ecccbe6f0180fbd026694533f7e9",
        "['layers']['qkv']['b']":
            "fbddbaf2890e64de54c06c4c0496215fc8cf67b85cca047ec8a6438416b97a7e",
        "['norm_f']['g']":
            "f46005cac3e16d680dbb57042a09e7bfade9f05733e8c168ebb10251a5a50aad",
    }, {
        16: "93e181da7367089378259b8be5eb82ee39e11127878a15e5cd437286bdff4d4c",
        8: "b3d521498ca2a528dc08198458e0e5eabc9d5f84e5cb5d215afad0daef8058a3",
    }),
}


def _sha(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()


@pytest.fixture(scope="module", params=list(CASES))
def case(request):
    extra, leaves, logits = CASES[request.param]
    model = tiny.TINY_MODEL | extra
    arch = spec.arch("dense_decoder")
    return arch, model, arch.make_weights(model, SEED, jnp.bfloat16), \
        leaves, logits


def test_weights_are_bitwise_those_made_before(case):
    _, _, params, leaves, _ = case
    got = {jax.tree_util.keystr(k): _sha(v)
           for k, v in jax.tree_util.tree_leaves_with_path(params)}
    assert got == leaves


@pytest.mark.parametrize("a_bits", [16, 8])
def test_reference_logits_are_bitwise_those_read_before(case, a_bits):
    arch, model, params, _, logits = case
    fwd = arch.make_forward(arch.sizes(model), a_bits=a_bits, w_bits=8)
    assert _sha(fwd(params, jnp.asarray(TOKENS))) == logits[a_bits]
