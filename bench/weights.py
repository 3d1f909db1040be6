"""Model weights made by the benchmark from the seed.

One jitted call on the device makes every leaf in the dtype it is served in
(matrices in the program's compute dtype, norm gains and biases in f32), in
the layout the program's transformer reads. The values come from the seed
alone, so the reference (``reference.py``) reads the same weights without
taking anything the program has made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def sizes(model: dict) -> dict:
    """Shapes of a dense decoder from the configuration file's keys."""
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    return {"L": model["num_hidden_layers"], "d": d, "H": h,
            "K": model["num_key_value_heads"],
            "hd": model.get("head_dim") or d // h,
            "ff": model["intermediate_size"], "V": model["vocab_size"],
            "qkv_bias": bool(model.get("attention_bias", False)),
            "theta": float(model["rope_theta"]),
            "eps": float(model["rms_norm_eps"])}


def key_of(seed: int, stream: int) -> jax.Array:
    """A JAX key for ``stream`` of ``seed`` (any non-negative int, also past
    32 bits)."""
    state = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.PRNGKey(int(state[0]) & 0x7FFFFFFF)


def make(model: dict, seed: int, matrix_dtype) -> dict:
    """Seeded weights: ``w ~ N(0, 1/d_in)``, embedding ``N(0, 0.02^2)``,
    norm gains ``1 + N(0, 0.1^2)``, QKV bias ``N(0, 0.02^2)``."""
    s = sizes(model)
    L, d, H, K, hd, ff, V = (s[k] for k in ("L", "d", "H", "K", "hd", "ff",
                                            "V"))
    qkv_out = (H + 2 * K) * hd

    def init(key):
        ks = iter(jax.random.split(key, 12))

        def mat(shape, fan_in):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    / np.sqrt(fan_in)).astype(matrix_dtype)

        def gain(shape):
            return 1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)

        layers = {
            "qkv": {"w": mat((L, d, qkv_out), d)},
            "attn_out": {"w": mat((L, H * hd, d), H * hd)},
            "norm_attn": {"g": gain((L, d))},
            "mlp": {"w_in": {"w": mat((L, d, 2 * ff), d)},
                    "w_out": {"w": mat((L, ff, d), ff)}},
            "norm_mlp": {"g": gain((L, d))},
        }
        if s["qkv_bias"]:
            layers["qkv"]["b"] = 0.02 * jax.random.normal(
                next(ks), (L, qkv_out), jnp.float32)
        emb = (0.02 * jax.random.normal(next(ks), (V, d), jnp.float32))
        return {"layers": layers, "norm_f": {"g": gain((d,))},
                "embed": {"w": emb.astype(matrix_dtype)}}

    return jax.jit(init)(key_of(seed, 0))


def check_layout(params: dict, expected) -> None:
    """The made tree has the structure, shapes and dtypes of ``expected``
    (the program's own parameter tree, as shapes only)."""
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), expected)
    if got != want:
        raise ValueError(f"benchmark weights do not match the program's "
                         f"layout:\n got  {got}\n want {want}")
