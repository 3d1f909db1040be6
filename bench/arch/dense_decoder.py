"""The dense pre-norm decoder: every layer is GQA attention over the paged
pool and a gated SiLU MLP; the output head is the tied embedding.

An architecture module is what the harness needs of one model family, found
by the name a configuration file gives under ``"arch"`` (``spec.arch``):

* ``sizes(model)`` — the shapes from the file's published keys, with
  ``"arch"`` (the module's name) and ``"L_attn"`` (layers whose KV lives in
  the paged pool);
* ``check_config(cfg, model)`` — the registry's ``ModelConfig``, after the
  file's overrides, runs what the file states, or ``ValueError``;
* ``make_weights(model, seed, dtype)`` — seeded weights in the program's
  layout, made on the device in one jitted call;
* ``make_forward(sz, *, a_bits, w_bits)`` — the plain reference;
* ``decode_token_flops(sz, ctx)`` and ``prefill_flops(sz, n)`` — the
  operations one row's decode step and one prompt need (``mfu.decode``
  reads them from the run's record).

``matmul_params`` and ``kv_bytes_per_token`` are this module's own helpers.

Reference layer: ``x += Wo . attn(rope(Wq h), rope(Wk h), Wv h)`` with
``h = rmsnorm(x)``; ``x += Wout (silu(g) * u)`` with ``[g | u] = Win
rmsnorm(x)``; logits ``= E . rmsnorm(x)``. Query head ``i`` reads KV head
``i // (H / K)``. RoPE rotates the two halves of each head (``[x1, x2] ->
[x1 c - x2 s, x2 c + x1 s]``, frequencies ``theta^(-2j/hd)``); text
positions make M-RoPE this same rotation. Precision is as
``reference.py`` states.
"""
from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import _rms, _rope, po2_fake_quant
from bench.weights import key_of

NAME = Path(__file__).stem


def sizes(model: dict) -> dict:
    """Shapes of a dense decoder from the configuration file's keys."""
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    L = model["num_hidden_layers"]
    return {"arch": NAME, "L": L, "L_attn": L, "d": d, "H": h,
            "K": model["num_key_value_heads"],
            "hd": model.get("head_dim") or d // h,
            "ff": model["intermediate_size"], "V": model["vocab_size"],
            "qkv_bias": bool(model.get("attention_bias", False)),
            "theta": float(model["rope_theta"]),
            "eps": float(model["rms_norm_eps"])}


def check_config(cfg, model: dict) -> None:
    """``cfg`` (the registry's, overrides applied) runs the file's sizes as
    this module's decoder: text only, full causal attention, RMS norms, a
    tied head, SiLU; M-RoPE only with the file's sections."""
    s = sizes(model)
    got = {"L": cfg.n_layers, "d": cfg.d_model, "H": cfg.n_heads,
           "K": cfg.n_kv, "hd": cfg.hd, "ff": cfg.d_ff, "V": cfg.vocab,
           "qkv_bias": cfg.qkv_bias, "theta": float(cfg.rope_theta)}
    want = {k: s[k] for k in got}
    sections = (model.get("rope_scaling") or {}).get("mrope_section")
    layout = {"frontend": cfg.frontend, "norm": cfg.norm,
              "causal": cfg.causal, "sliding_window": cfg.sliding_window,
              "act": cfg.act, "tie_embeddings": cfg.tie_embeddings,
              "mrope_sections": tuple(cfg.mrope_sections) if cfg.mrope
              else None}
    need = {"frontend": None, "norm": "rms", "causal": True,
            "sliding_window": 0, "act": "silu", "tie_embeddings": True,
            "mrope_sections": tuple(sections) if sections else None}
    if got != want or layout != need or cfg.family not in ("dense", "vlm"):
        raise ValueError(f"registry {cfg.name} ({cfg.family}) runs {got} "
                         f"{layout}, the configuration file states {want} "
                         f"{need}")


def make_weights(model: dict, seed: int, matrix_dtype) -> dict:
    """Seeded weights: ``w ~ N(0, 1/d_in)``, embedding ``N(0, 0.02^2)``,
    norm gains ``1 + N(0, 0.1^2)``, QKV bias ``N(0, 0.02^2)``; matrices in
    ``matrix_dtype``, gains and biases in f32."""
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


def make_forward(sz: dict, *, a_bits: int, w_bits: int):
    """``forward(params, tokens [T]) -> logits [T, V]`` (f32), jitted. The
    layers run one at a time under ``lax.scan``, so only one layer's f32
    weights exist at once."""
    L, d, H, K, hd, ff = (sz[k] for k in ("L", "d", "H", "K", "hd", "ff"))
    G = H // K
    eps, theta = sz["eps"], sz["theta"]

    def act(x):
        return po2_fake_quant(x, a_bits, axis=-1)

    def wq(w):
        return po2_fake_quant(w.astype(jnp.float32), w_bits)

    def forward(params, tokens):
        with jax.default_matmul_precision("highest"):
            t = tokens.shape[0]
            pos = jnp.arange(t, dtype=jnp.int32)
            emb = wq(params["embed"]["w"])                     # [V, d]
            x = emb[tokens]
            causal = pos[:, None] >= pos[None, :]

            def layer(x, lp):
                h = _rms(x, lp["norm_attn"]["g"], eps)
                qkv = act(h) @ wq(lp["qkv"]["w"])
                if "b" in lp["qkv"]:
                    qkv = qkv + lp["qkv"]["b"]
                q = qkv[:, :H * hd].reshape(t, H, hd)
                k = qkv[:, H * hd:(H + K) * hd].reshape(t, K, hd)
                v = qkv[:, (H + K) * hd:].reshape(t, K, hd)
                q, k = _rope(q, pos, theta), _rope(k, pos, theta)
                k = k.astype(jnp.bfloat16).astype(jnp.float32)   # kv16
                v = v.astype(jnp.bfloat16).astype(jnp.float32)
                qg = q.reshape(t, K, G, hd)
                s = jnp.einsum("tkgd,ukd->kgtu", qg, k) / np.sqrt(hd)
                s = jnp.where(causal[None, None], s, -jnp.inf)
                p = jax.nn.softmax(s, axis=-1)
                o = jnp.einsum("kgtu,ukd->tkgd", p, v).reshape(t, H * hd)
                x = x + act(o) @ wq(lp["attn_out"]["w"])
                h = _rms(x, lp["norm_mlp"]["g"], eps)
                gu = act(h) @ wq(lp["mlp"]["w_in"]["w"])
                a = jax.nn.silu(gu[:, :ff]) * gu[:, ff:]
                x = x + act(a) @ wq(lp["mlp"]["w_out"]["w"])
                return x, None

            x, _ = jax.lax.scan(layer, x, params["layers"])
            x = _rms(x, params["norm_f"]["g"], eps)
            return act(x) @ emb.T

    return jax.jit(forward)


def matmul_params(sz: dict) -> int:
    """Weights one token multiplies through: the layers' projections and
    the (tied) output head. The embedding gather does no arithmetic."""
    d, H, K, hd, ff = sz["d"], sz["H"], sz["K"], sz["hd"], sz["ff"]
    layer = d * (H + 2 * K) * hd + H * hd * d + d * 2 * ff + ff * d
    return sz["L"] * layer + d * sz["V"]


def _attn_flops(sz: dict, ctx: int) -> int:
    """One query over ``ctx`` keys, all layers: ``q.k`` and ``p.v``."""
    return 4 * ctx * sz["H"] * sz["hd"] * sz["L"]


def decode_token_flops(sz: dict, ctx: int) -> int:
    """One decode step of one row that attends over ``ctx`` keys."""
    return 2 * matmul_params(sz) + _attn_flops(sz, ctx)


def prefill_flops(sz: dict, n: int) -> int:
    """A causal prompt of ``n`` tokens: query ``i`` attends over ``i + 1``
    keys."""
    return 2 * matmul_params(sz) * n + 4 * sz["H"] * sz["hd"] * sz["L"] \
        * (n * (n + 1) // 2)


def kv_bytes_per_token(sz: dict, kv_bits: int) -> int:
    """Key and value of one token in every layer's pool."""
    return 2 * sz["L"] * sz["K"] * sz["hd"] * kv_bits // 8
