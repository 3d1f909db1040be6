"""Plain reference of the served model, and the gap the check compares.

A dense decoder written out in ``jax.numpy`` at float32 with
``default_matmul_precision("highest")``: no cache, no kernels, no batching,
one causal pass over a whole sequence. It imports nothing of the program
and reads only the weights the benchmark made (``weights.py``).

It computes at the precision the configuration states: every matrix on its
per-tensor power-of-two ``W``-bit grid, every matmul input on a per-token
power-of-two ``A``-bit grid (signed, range ``[-2^(b-1), 2^(b-1) - 1]``,
scale ``2^ceil(log2(amax / 2^(b-1)))``), the embedding table on the weight
grid, the tied output head on the embedding's grid, and keys and values
rounded to the cache's 16-bit float (kv16). Everything else is f32.

Layer (pre-norm): ``x += Wo . attn(rope(Wq h), rope(Wk h), Wv h)`` with
``h = rmsnorm(x)``; ``x += Wout (silu(g) * u)`` with ``[g | u] = Win
rmsnorm(x)``; logits ``= E . rmsnorm(x)``. Query head ``i`` reads KV head
``i // (H / K)``. RoPE rotates the two halves of each head (``[x1, x2] ->
[x1 c - x2 s, x2 c + x1 s]``, frequencies ``theta^(-2j/hd)``); text
positions make M-RoPE this same rotation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def po2_fake_quant(x: jax.Array, bits: int, axis=None) -> jax.Array:
    """Round ``x`` onto the signed power-of-two ``bits`` grid, per tensor
    (``axis=None``) or per row of ``axis``."""
    qmax = 2.0 ** (bits - 1) - 1.0
    qmin = -(qmax + 1.0)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    amax = jnp.maximum(amax, 1e-9)
    scale = jnp.exp2(jnp.ceil(jnp.log2(amax / (qmax + 1.0))))
    q = jnp.clip(jnp.sign(x / scale) * jnp.floor(jnp.abs(x / scale) + 0.5),
                 qmin, qmax)
    return q * scale


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv           # [T, hd/2]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


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


def served_gaps(logits: np.ndarray, served: np.ndarray) -> np.ndarray:
    """Per served token: how far its reference logit lies below the
    reference's best at that position (0 where it is the best)."""
    rows = np.arange(len(served))
    return logits.max(axis=-1) - logits[rows, served]


def teacher_forced(prompt: np.ndarray, served: np.ndarray, pad_to: int):
    """The sequence the reference reads (prompt, then every served token
    but the last), padded at the end to ``pad_to``, and the positions whose
    logits predict the served tokens."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} exceeds pad {pad_to}")
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(seq)] = seq
    first = len(prompt) - 1
    return padded, np.arange(first, first + len(served))
