"""What every architecture's plain reference shares, and the gap the check
compares.

Each ``arch/<name>.py`` writes its model out in ``jax.numpy`` at float32
with ``default_matmul_precision("highest")`` (``make_forward``): no cache,
no kernels, no batching, one causal pass over a whole sequence. It imports
nothing of the program and reads only the weights the benchmark made.

It computes at the precision the configuration states: every matrix on its
per-tensor power-of-two ``W``-bit grid, every matmul input on a per-token
power-of-two ``A``-bit grid (signed, range ``[-2^(b-1), 2^(b-1) - 1]``,
scale ``2^ceil(log2(amax / 2^(b-1)))``, ``po2_fake_quant``), the embedding
table on the weight grid, the tied output head on the embedding's grid, and
keys and values rounded to the cache's 16-bit float (kv16). Everything else
is f32.
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
