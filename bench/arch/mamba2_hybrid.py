"""The interleaved Mamba-2 / attention decoder (granitemoehybrid without
experts): each layer is a Mamba-2 mixer or a GQA attention mixer, by the
configuration's ``layer_types``, and then a gated SiLU MLP; the output head
is the tied embedding. The module supplies what ``arch/dense_decoder.py``
lists, and ``ssm_decode_call`` (one row's state update in one layer, which
``metrics/ssm_decode_roofline.decode.py`` reads).

Reference layer, with ``rms`` the RMS norm: ``x += mixer(rms(x))``;
``x += Wout (silu(g) * u)`` with ``[g | u] = Win rms(x)``; logits ``= E .
rms(x)``.

* Attention: GQA over all earlier positions with no positional encoding
  (NoPE), scale ``1/sqrt(hd)``; query head ``i`` reads KV head ``i // (H /
  K)``; keys and values rounded to the cache's 16-bit float.
* Mamba-2: ``[z | xBC | dt] = Win h``; ``xBC`` through a causal depthwise
  conv of width ``K`` with bias, then SiLU, split into ``x [T, nh, P]``,
  ``B, C [T, G, N]`` (head ``i`` reads group ``i // (nh / G)``); ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; SSD in its quadratic
  (masked-attention) form over the whole sequence,
  ``y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s + D x_t``;
  then ``rms(y * silu(z))`` (one group over the whole inner width) and
  ``Wout``. This shares nothing with the program's chunked scan or its
  recurrent step.

Precision is as ``reference.py`` states; the conv, ``A_log``, ``D`` and
``dt_bias`` are f32 and not quantized, as in the program.
"""
from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import _rms, po2_fake_quant
from bench.weights import key_of

NAME = Path(__file__).stem


def sizes(model: dict) -> dict:
    """Shapes of the stack from the configuration file's keys."""
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    kinds = tuple(model["layer_types"])
    di = model["mamba_expand"] * d
    G, N = model["mamba_n_groups"], model["mamba_d_state"]
    return {"arch": NAME, "L": model["num_hidden_layers"],
            "L_attn": kinds.count("attention"), "layer_types": kinds,
            "d": d, "H": h, "K": model["num_key_value_heads"],
            "hd": model.get("head_dim") or d // h,
            "ff": model["shared_intermediate_size"],
            "V": model["vocab_size"], "eps": float(model["rms_norm_eps"]),
            "di": di, "nh": model["mamba_n_heads"],
            "P": model["mamba_d_head"], "N": N, "G": G,
            "conv": model["mamba_d_conv"], "cd": di + 2 * G * N,
            "chunk": model["mamba_chunk_size"]}


def check_config(cfg, model: dict) -> None:
    """``cfg`` (the registry's, overrides applied) runs the file's stack:
    the layer pattern, NoPE attention, the Mamba-2 and MLP widths, RMS
    norms, a tied head, SiLU, conv bias and no projection bias."""
    s = sizes(model)
    sc = cfg.ssm
    got = {"L": cfg.n_layers, "layer_types": tuple(cfg.layer_types or ()),
           "d": cfg.d_model, "H": cfg.n_heads, "K": cfg.n_kv, "hd": cfg.hd,
           "ff": cfg.d_ff, "V": cfg.vocab,
           "di": sc.d_inner(cfg.d_model) if sc else None,
           "nh": sc.n_heads(cfg.d_model) if sc else None,
           "P": sc and sc.head_dim, "N": sc and sc.d_state,
           "G": sc and sc.n_groups, "conv": sc and sc.d_conv,
           "chunk": sc and sc.chunk}
    want = {k: s[k] for k in got}
    layout = {"family": cfg.family, "rope": cfg.rope,
              "frontend": cfg.frontend, "norm": cfg.norm,
              "causal": cfg.causal, "sliding_window": cfg.sliding_window,
              "act": cfg.act, "tie_embeddings": cfg.tie_embeddings,
              "qkv_bias": cfg.qkv_bias, "mlp_act": model["hidden_act"],
              "conv_bias": model["mamba_conv_bias"],
              "proj_bias": model["mamba_proj_bias"],
              "experts": model["num_local_experts"],
              "pos": model["position_embedding_type"],
              "norm_fn": model["normalization_function"]}
    need = {"family": "interleaved", "rope": False, "frontend": None,
            "norm": "rms", "causal": True, "sliding_window": 0, "act": "silu",
            "tie_embeddings": bool(model["tie_word_embeddings"]),
            "qkv_bias": bool(model["attention_bias"]), "mlp_act": "silu",
            "conv_bias": True, "proj_bias": False, "experts": 0,
            "pos": "nope", "norm_fn": "rmsnorm"}
    if got != want or layout != need:
        raise ValueError(f"registry {cfg.name} runs {got} {layout}, the "
                         f"configuration file states {want} {need}")


def make_weights(model: dict, seed: int, matrix_dtype) -> dict:
    """Seeded weights: matrices ``N(0, 1/d_in)``, embedding ``N(0,
    0.02^2)``, norm gains (the gated norm's too) ``1 + N(0, 0.1^2)``; conv
    ``N(0, 1/K)`` with bias ``N(0, 0.1^2)``, ``A_log = log(1..nh)``,
    ``dt_bias`` the softplus inverse of a log-uniform ``dt`` in [1e-3,
    1e-1], ``D = 1 + N(0, 0.1^2)``; matrices in ``matrix_dtype``, the rest
    in f32."""
    s = sizes(model)
    d, H, K, hd, ff, V = (s[k] for k in ("d", "H", "K", "hd", "ff", "V"))
    di, nh, G, N, cw, cd = (s[k] for k in ("di", "nh", "G", "N", "conv",
                                           "cd"))
    la = s["L_attn"]
    lm = s["L"] - la

    def init(key):
        ks = iter(jax.random.split(key, 24))

        def mat(shape, fan_in):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    / np.sqrt(fan_in)).astype(matrix_dtype)

        def normal(shape, std):
            return std * jax.random.normal(next(ks), shape, jnp.float32)

        def gain(shape):
            return 1.0 + normal(shape, 0.1)

        def mlp(n):
            return {"w_in": {"w": mat((n, d, 2 * ff), d)},
                    "w_out": {"w": mat((n, ff, d), ff)}}

        dt = jnp.exp(jax.random.uniform(next(ks), (lm, nh), jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        attention = {
            "qkv": {"w": mat((la, d, (H + 2 * K) * hd), d)},
            "attn_out": {"w": mat((la, H * hd, d), H * hd)},
            "norm_attn": {"g": gain((la, d))},
            "mlp": mlp(la), "norm_mlp": {"g": gain((la, d))}}
        mamba = {
            "ssm": {"in_proj": {"w": mat((lm, d, 2 * di + 2 * G * N + nh),
                                         d)},
                    "out_proj": {"w": mat((lm, di, d), di)},
                    "conv_w": normal((lm, cw, cd), 1.0 / np.sqrt(cw)),
                    "conv_b": normal((lm, cd), 0.1),
                    "A_log": jnp.broadcast_to(
                        jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
                        (lm, nh)),
                    "D": gain((lm, nh)),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "norm_g": gain((lm, di))},
            "norm_ssm": {"g": gain((lm, d))},
            "mlp": mlp(lm), "norm_mlp": {"g": gain((lm, d))}}
        emb = normal((V, d), 0.02)
        return {"layers": {"attention": attention, "mamba": mamba},
                "norm_f": {"g": gain((d,))},
                "embed": {"w": emb.astype(matrix_dtype)}}

    return jax.jit(init)(key_of(seed, 0))


def make_forward(sz: dict, *, a_bits: int, w_bits: int, kv_bits: int = 16):
    """``forward(params, tokens [T]) -> logits [T, V]`` (f32), jitted. One
    scan runs the layers in depth order; each step takes its layer's
    weights out of its kind's stack, so only one layer's f32 weights exist
    at once. ``kv_bits=32`` keeps keys and values in f32 (a pool of f32)
    instead of rounding them to the 16-bit cache."""
    H, K, hd, ff = (sz[k] for k in ("H", "K", "hd", "ff"))
    di, nh, P, N, G, cw = (sz[k] for k in ("di", "nh", "P", "N", "G",
                                           "conv"))
    eps = sz["eps"]
    kinds = np.asarray([k == "attention" for k in sz["layer_types"]])
    index = np.where(kinds, np.cumsum(kinds) - 1, np.cumsum(~kinds) - 1)

    def act(x):
        return po2_fake_quant(x, a_bits, axis=-1)

    def wq(w):
        return po2_fake_quant(w.astype(jnp.float32), w_bits)

    def mlp(x, lp):
        gu = act(_rms(x, lp["norm_mlp"]["g"], eps)) @ wq(lp["mlp"]["w_in"]["w"])
        a = jax.nn.silu(gu[:, :ff]) * gu[:, ff:]
        return x + act(a) @ wq(lp["mlp"]["w_out"]["w"])

    def attention(x, lp, causal):
        t = x.shape[0]
        qkv = act(_rms(x, lp["norm_attn"]["g"], eps)) @ wq(lp["qkv"]["w"])
        q = qkv[:, :H * hd].reshape(t, K, H // K, hd)
        k = qkv[:, H * hd:(H + K) * hd].reshape(t, K, hd)
        v = qkv[:, (H + K) * hd:].reshape(t, K, hd)
        if kv_bits == 16:
            k = k.astype(jnp.bfloat16).astype(jnp.float32)
            v = v.astype(jnp.bfloat16).astype(jnp.float32)
        s = jnp.einsum("tkgd,ukd->kgtu", q, k) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), -1)
        o = jnp.einsum("kgtu,ukd->tkgd", p, v).reshape(t, H * hd)
        return mlp(x + act(o) @ wq(lp["attn_out"]["w"]), lp)

    def mamba(x, lp, causal):
        t = x.shape[0]
        m = lp["ssm"]
        proj = act(_rms(x, lp["norm_ssm"]["g"], eps)) @ wq(m["in_proj"]["w"])
        z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * G * N], \
            proj[:, 2 * di + 2 * G * N:]
        pad = jnp.concatenate([jnp.zeros((cw - 1, xbc.shape[1])), xbc])
        xbc = jax.nn.silu(sum(pad[i:i + t] * m["conv_w"][i]
                              for i in range(cw)) + m["conv_b"])
        xs = xbc[:, :di].reshape(t, nh, P)
        grp = np.arange(nh) // (nh // G)
        b = xbc[:, di:di + G * N].reshape(t, G, N)[:, grp]   # [T, nh, N]
        c = xbc[:, di + G * N:].reshape(t, G, N)[:, grp]
        dt = jax.nn.softplus(dt + m["dt_bias"])              # [T, nh]
        cum = jnp.cumsum(dt * -jnp.exp(m["A_log"]), axis=0)   # [T, nh]
        seg = jnp.where(causal[None], cum.T[:, :, None] - cum.T[:, None, :],
                        -jnp.inf)                            # [nh, t, s]
        w = jnp.einsum("thn,shn->hts", c, b) * jnp.exp(seg)
        y = jnp.einsum("hts,sh,shp->thp", w, dt, xs) + m["D"][:, None] * xs
        y = y.reshape(t, di) * jax.nn.silu(z)
        y = _rms(y, m["norm_g"], eps)
        return mlp(x + act(y) @ wq(m["out_proj"]["w"]), lp)

    def forward(params, tokens):
        with jax.default_matmul_precision("highest"):
            t = tokens.shape[0]
            pos = jnp.arange(t, dtype=jnp.int32)
            causal = pos[:, None] >= pos[None, :]
            emb = wq(params["embed"]["w"])                     # [V, d]
            stacks = params["layers"]

            def layer(x, xs):
                is_attn, j = xs
                return jax.lax.cond(
                    is_attn,
                    lambda x: attention(x, jax.tree.map(
                        lambda a: a[j], stacks["attention"]), causal),
                    lambda x: mamba(x, jax.tree.map(
                        lambda a: a[j], stacks["mamba"]), causal), x), None

            x, _ = jax.lax.scan(layer, emb[tokens],
                                (jnp.asarray(kinds), jnp.asarray(index)))
            x = _rms(x, params["norm_f"]["g"], eps)
            return act(x) @ emb.T

    return jax.jit(forward)


def matmul_params(sz: dict) -> int:
    """Weights one token multiplies through: each layer's mixer
    projections and MLP, and the (tied) output head."""
    d, H, K, hd, ff = sz["d"], sz["H"], sz["K"], sz["hd"], sz["ff"]
    di, G, N, nh = sz["di"], sz["G"], sz["N"], sz["nh"]
    attn = d * (H + 2 * K) * hd + H * hd * d
    mamba = d * (2 * di + 2 * G * N + nh) + di * d
    la = sz["L_attn"]
    return (la * attn + (sz["L"] - la) * mamba + sz["L"] * 3 * d * ff
            + d * sz["V"])


def _ssm_token_ops(sz: dict) -> int:
    """One token's recurrent work in one Mamba layer: per head the decay,
    the input's outer product and their sum (3 P N), ``h . C`` (2 P N) and
    the D skip (2 P); the conv window (2 K per channel)."""
    P, N = sz["P"], sz["N"]
    return sz["nh"] * (5 * P * N + 2 * P) + 2 * sz["conv"] * sz["cd"]


def decode_token_flops(sz: dict, ctx: int) -> int:
    """One decode step of one row whose attention layers read ``ctx``
    keys."""
    return (2 * matmul_params(sz) + 4 * ctx * sz["H"] * sz["hd"] * sz["L_attn"]
            + (sz["L"] - sz["L_attn"]) * _ssm_token_ops(sz))


def prefill_flops(sz: dict, n: int) -> int:
    """A causal prompt of ``n`` tokens: token ``i`` attends over ``i + 1``
    keys and takes one recurrent step in each Mamba layer."""
    return (n * (2 * matmul_params(sz)
                 + (sz["L"] - sz["L_attn"]) * _ssm_token_ops(sz))
            + 4 * sz["H"] * sz["hd"] * sz["L_attn"] * (n * (n + 1) // 2))


def ssm_decode_call(sz: dict) -> tuple[int, int]:
    """(operations, bytes) of one row's state-update kernel call in one
    layer: the state update without the conv. Bytes: the f32 state read
    and written, and x, dt, B, C and y (f32)."""
    nh, P, N, G = sz["nh"], sz["P"], sz["N"], sz["G"]
    ops = nh * (5 * P * N + 2 * P)
    byts = 4 * (2 * nh * P * N + nh * P + nh + 2 * G * N + nh * P)
    return ops, byts
