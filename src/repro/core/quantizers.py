"""Quantizers: fake-quant (QAT, straight-through) and native integer quantization.

Two regimes, sharing :class:`~repro.core.qtypes.QuantSpec` so a QAT checkpoint
deploys unchanged to the native inference path (DESIGN §8.3):

* ``fake_quant``      — float-in/float-out quantize→dequantize with a
  straight-through estimator; used during quantization-aware training exactly
  like QKeras/Brevitas in the paper.
* ``quantize_native`` / ``dequantize`` — produce/consume integer carriers
  (int8, packed int4) for the serving path and the Pallas kernels, cutting the
  HBM/collective roofline terms.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .qtypes import (
    QuantSpec,
    carrier_dtype,
    compute_scale,
    pack_int4,
    qrange,
    qrange_dynamic,
    unpack_int4,
)

__all__ = [
    "fake_quant",
    "fake_quant_dynamic",
    "fake_quant_dynamic_token",
    "quantize_native",
    "po2_carrier",
    "dequantize",
    "QTensor",
]


def _round(x: jax.Array, stochastic: bool, key: Optional[jax.Array]) -> jax.Array:
    if not stochastic:
        # round-half-away-from-zero: matches HLS AP_RND behaviour and is
        # symmetric in sign, unlike jnp.round's banker's rounding.
        return jnp.sign(x) * jnp.floor(jnp.abs(x) + 0.5)
    assert key is not None, "stochastic rounding needs a PRNG key"
    noise = jax.random.uniform(key, x.shape, dtype=x.dtype)
    return jnp.floor(x + noise)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def fake_quant(x: jax.Array, spec: QuantSpec, scale: Optional[jax.Array] = None,
               key: Optional[jax.Array] = None) -> jax.Array:
    """Quantize→dequantize ``x`` onto the grid of ``spec`` (float in/out).

    If ``scale`` is None it is calibrated on the fly from ``max|x|`` (the
    dynamic-quantization used for activations at training time); passing a
    fixed scale reproduces static fixed-point behaviour.
    Gradient: straight-through inside the clip range, zero outside.
    """
    y, _ = _fake_quant_fwd(x, spec, scale, key)
    return y


def _fake_quant_impl(x, spec: QuantSpec, scale, key):
    if spec.is_float:
        return x, None
    dt = x.dtype
    xf = x.astype(jnp.float32)
    s = compute_scale(xf, spec) if scale is None else jnp.asarray(scale, jnp.float32)
    qmin, qmax = qrange(spec)
    q = jnp.clip(_round(xf / s, spec.stochastic, key), qmin, qmax)
    lo, hi = qmin * s, qmax * s  # pass-through band for the STE mask
    return (q * s).astype(dt), (xf, lo, hi)


def _fake_quant_fwd(x, spec, scale, key):
    y, res = _fake_quant_impl(x, spec, scale, key)
    return y, res


def _fake_quant_bwd(spec, res, g):
    if res is None:  # float passthrough
        return (g, None, None)
    xf, lo, hi = res
    mask = ((xf >= lo) & (xf <= hi)).astype(g.dtype)
    return (g * mask, None, None)


fake_quant.defvjp(_fake_quant_fwd, _fake_quant_bwd)


@jax.custom_vjp
def fake_quant_dynamic(x: jax.Array, bits: jax.Array, signed_sym: jax.Array) -> jax.Array:
    """Fake-quant with *traced* bit-width (spec-as-data; DESIGN §8.2).

    Used inside ``lax.scan`` over stacked layers where each layer row carries
    its own (possibly different) precision — the branch-free realization of the
    paper's per-layer mixed precision. ``bits >= 17`` rows degrade to identity.
    ``signed_sym`` is a (2,) int array [signed, symmetric] kept as data for
    completeness; current model code always uses signed, non-symmetric.
    """
    y, _ = _fqd_fwd(x, bits, signed_sym)
    return y


def _po2_grid(xf, bits, axis=None):
    """Integer grid values ``q`` and power-of-two ``scale`` of the dynamic
    signed, non-symmetric fake-quant (``q * scale`` is its output)."""
    qmin, qmax = qrange_dynamic(bits, signed=True, symmetric=False)
    if axis is None:
        amax = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-9)
    else:
        amax = jnp.maximum(jnp.max(jnp.abs(xf), axis=axis, keepdims=True), 1e-9)
    scale = jnp.exp2(jnp.ceil(jnp.log2(amax / jnp.maximum(-qmin, qmax))))
    q = jnp.clip(jnp.sign(xf / scale) * jnp.floor(jnp.abs(xf / scale) + 0.5), qmin, qmax)
    return q, scale, qmin, qmax


def _fqd_impl(x, bits, signed_sym, axis=None):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    q, scale, qmin, qmax = _po2_grid(xf, bits, axis)
    y = q * scale
    passthrough = (bits >= 17).astype(jnp.float32)
    y = passthrough * xf + (1.0 - passthrough) * y
    lo, hi = qmin * scale, qmax * scale
    mask = passthrough + (1.0 - passthrough) * ((xf >= lo) & (xf <= hi)).astype(jnp.float32)
    return y.astype(dt), mask


def _fqd_fwd(x, bits, signed_sym):
    y, mask = _fqd_impl(x, bits, signed_sym)
    return y, mask


def _fqd_bwd(mask, g):
    return (g * mask.astype(g.dtype), None, None)


fake_quant_dynamic.defvjp(_fqd_fwd, _fqd_bwd)


@jax.custom_vjp
def fake_quant_dynamic_token(x: jax.Array, bits: jax.Array,
                             signed_sym: jax.Array) -> jax.Array:
    """Per-token :func:`fake_quant_dynamic`: the pow2 grid is chosen from each
    trailing-axis row's own ``amax`` instead of the whole tensor's.

    Activation quantization uses this so a token's values depend **only on that
    token** — a row's decode numerics become invariant to batch composition and
    to how many positions share the forward pass. That invariance is what makes
    speculative verify windows (``[B, k+1]``) bit-identical to the stepwise
    ``[B, 1]`` greedy decode (docs/serving.md, invariant 11): a per-tensor amax
    would couple every window position (and every batch row) through one shared
    scale, flipping pow2 buckets whenever a *neighbouring* token's range grows.
    For 1-D inputs this is exactly ``fake_quant_dynamic``. Weight quantization
    keeps the per-tensor grid (weights are identical across paths anyway).
    """
    y, _ = _fqd_fwd_token(x, bits, signed_sym)
    return y


def _fqd_fwd_token(x, bits, signed_sym):
    return _fqd_impl(x, bits, signed_sym, axis=-1)


fake_quant_dynamic_token.defvjp(_fqd_fwd_token, _fqd_bwd)


class QTensor(NamedTuple):
    """A natively quantized tensor: integer carrier + scale (+ static spec info).

    ``data`` is int8 (int4 values packed two-per-byte when ``bits <= 4``);
    ``scale`` broadcasts against the *dequantized* shape. ``bits`` and the
    original trailing dim ``orig_last`` ride in static fields of the pytree.
    """

    data: jax.Array
    scale: jax.Array
    bits: int
    orig_last: int

    @property
    def shape(self):
        if self.bits <= 4:
            return (*self.data.shape[:-1], self.orig_last)
        return self.data.shape


def quantize_native(x: jax.Array, spec: QuantSpec, scale: Optional[jax.Array] = None) -> QTensor:
    """Quantize to an integer carrier for storage/serving (no gradient path)."""
    assert not spec.is_float
    xf = x.astype(jnp.float32)
    s = compute_scale(xf, spec) if scale is None else jnp.asarray(scale, jnp.float32)
    qmin, qmax = qrange(spec)
    q = jnp.clip(jnp.sign(xf / s) * jnp.floor(jnp.abs(xf / s) + 0.5), qmin, qmax)
    if spec.bits <= 4:
        data = pack_int4(q.astype(jnp.int8))
    else:
        data = q.astype(carrier_dtype(spec.bits))
    return QTensor(data=data, scale=s, bits=spec.bits, orig_last=x.shape[-1])


def po2_carrier(x: jax.Array, bits: jax.Array) -> QTensor:
    """Int8 carrier of ``fake_quant_dynamic(x, bits)`` for ``bits <= 8``.

    The per-tensor grid's scale is a power of two, so :func:`dequantize` of
    the carrier reproduces the fake-quant values exactly, in any float dtype
    that holds the 8-bit grid (f32, bf16). ``bits`` may be traced.
    """
    q, scale, _, _ = _po2_grid(x.astype(jnp.float32), bits)
    return QTensor(q.astype(jnp.int8), scale, 8, x.shape[-1])


def dequantize(qt: QTensor, dtype=jnp.bfloat16) -> jax.Array:
    """Dequantize a :class:`QTensor` back to floats (the jnp reference path;
    the Pallas kernel fuses this into the matmul)."""
    q = unpack_int4(qt.data) if qt.bits <= 4 else qt.data
    return (q.astype(jnp.float32) * qt.scale).astype(dtype)


jax.tree_util.register_pytree_node(
    QTensor,
    lambda t: ((t.data, t.scale), (t.bits, t.orig_last)),
    lambda aux, ch: QTensor(ch[0], ch[1], aux[0], aux[1]),
)
