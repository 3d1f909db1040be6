"""Quantization-aware building blocks.

Every matmul in the model zoo runs through :func:`qlinear` so the paper's
per-layer ``Ax-Wy`` profiles apply uniformly to all ten architectures. Two
execution modes share one parameter layout:

* **fake mode** (QAT / paper-faithful semantics): master weights stay float;
  activations and weights are fake-quantized with *traced* bit-widths
  (``bits_aw`` is data → the merged adaptive engine is branch-free).
* **native mode** (serving): weights are pre-quantized integer carriers
  (:class:`QTensor`); compute dequantizes on the fly (Pallas kernel on TPU,
  jnp reference elsewhere — identical roofline terms).

``bits_aw`` is an int32 ``[2]`` (a_bits, w_bits); bits ≥ 17 = float passthrough.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantizers import (QTensor, dequantize, fake_quant_dynamic,
                                   fake_quant_dynamic_token)
from repro.core.qtypes import QuantSpec
from repro.core.quantizers import quantize_native
from repro.runtime import compute_dtype as _default_compute_dtype

__all__ = [
    "qlinear", "init_linear", "quantize_linear_native",
    "rms_norm", "layer_norm", "init_norm",
    "embed_lookup", "init_embed",
    "SIGNED_SYM",
]

SIGNED_SYM = np.array([1, 0], np.int32)  # fixed (signed, non-symmetric) grid


# ---------------------------------------------------------------------------
# linear
# ---------------------------------------------------------------------------

def init_linear(key: jax.Array, d_in: int, d_out: int, *, bias: bool = False,
                scale: float | None = None, dtype=jnp.float32) -> dict:
    s = (1.0 / np.sqrt(d_in)) if scale is None else scale
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * s).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def qlinear(params: dict, x: jax.Array, bits_aw: jax.Array, *,
            compute_dtype=None) -> jax.Array:
    """Quantization-aware linear: fake mode (float master weights) or native
    mode (integer carriers), switched on the parameter layout.

    Fake mode keys: ``w`` [in,out] (+ ``b``). Native keys: ``wq`` (QTensor
    leaves as ``wq_data``/``wq_scale`` + static bits in ``wq_bits``) (+ ``b``).

    Activations quantize **per token** (trailing-axis amax): each row's grid
    depends only on that row, so decode numerics are invariant to batch
    composition and to the speculative verify width (invariant 11). Weights
    keep the per-tensor grid.
    """
    if compute_dtype is None:
        compute_dtype = _default_compute_dtype()
    # Activations quantize in-loop (their scale depends on runtime data);
    # the weight side is either already on its grid or fake-quanted here.
    xq = fake_quant_dynamic_token(x, bits_aw[0], SIGNED_SYM)
    if "wfq" in params:
        # Decode-scan image: the weight was fake-quanted *once* ahead of the
        # loop (transformer.prequant_decode_weights) instead of every step.
        w = params["wfq"].astype(compute_dtype)
    elif "wq" in params:
        # Integer carrier: a native deployment layout, or a decode-scan image
        # grafted next to the float master (it wins over ``w``).
        w = dequantize(params["wq"], compute_dtype)
    else:
        w = fake_quant_dynamic(params["w"], bits_aw[1],
                               SIGNED_SYM).astype(compute_dtype)
    y = jnp.dot(xq.astype(compute_dtype), w, preferred_element_type=jnp.float32)
    if "b" in params:
        y = y + params["b"].astype(jnp.float32)
    return y.astype(compute_dtype)


def quantize_linear_native(params: dict, w_bits: int = 8) -> dict:
    """Convert a fake-mode linear to native integer storage (deployment)."""
    spec = QuantSpec(bits=w_bits, per_channel=True, channel_axis=-1, po2_scale=False)
    out = {"wq": quantize_native(params["w"], spec)}
    if "b" in params:
        out["b"] = params["b"]
    return out


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(d: int, *, bias: bool = False) -> dict:
    p = {"g": jnp.ones((d,), jnp.float32)}
    if bias:
        p["b"] = jnp.zeros((d,), jnp.float32)
    return p


def rms_norm(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * params["g"]
    return y.astype(x.dtype)


def layer_norm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * params["g"] + params.get("b", 0.0)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# embedding
# ---------------------------------------------------------------------------

def init_embed(key: jax.Array, vocab: int, d: int, dtype=jnp.float32) -> dict:
    return {"w": jax.random.normal(key, (vocab, d), jnp.float32).astype(dtype) * 0.02}


def embed_lookup(params: dict, ids: jax.Array, bits_aw: jax.Array,
                 compute_dtype=None) -> jax.Array:
    """Embedding gather with weight-only quantization (a_bits doesn't apply to
    an integer gather — the paper's data approximation acts on the table)."""
    if compute_dtype is None:
        compute_dtype = _default_compute_dtype()
    if "wq" in params:  # int carrier: gather int rows, dequant after (HBM win)
        from repro.core.qtypes import unpack_int4
        qt: QTensor = params["wq"]
        rows = jnp.take(qt.data, ids, axis=0)
        if qt.bits <= 4:
            rows = unpack_int4(rows)
        return (rows.astype(jnp.float32) * qt.scale).astype(compute_dtype)
    if "wfq" in params:  # decode scan: table fake-quanted ahead of the loop
        return jnp.take(params["wfq"].astype(compute_dtype), ids, axis=0)
    w = fake_quant_dynamic(params["w"], bits_aw[1], SIGNED_SYM)
    return jnp.take(w.astype(compute_dtype), ids, axis=0)
