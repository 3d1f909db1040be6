"""Mamba2 — State-Space Duality (SSD) block, chunked matmul form + recurrent decode.

The chunked SSD algorithm (Dao & Gu, 2024) is MXU-friendly by construction:
intra-chunk terms are ``[Q, Q]``/``[Q, N]`` matmuls and the inter-chunk
recurrence is a short ``lax.scan`` over ``S/Q`` chunk states — exactly the
compute shape TPUs want, so the paper's GPU-oriented kernels are *adapted*
(DESIGN §2) rather than ported. Decode is the O(1)-state recurrence, which is
what makes the ``long_500k`` cell runnable for SSM/hybrid archs.

Projections run through the quantized path (sites ``ssm_in`` / ``ssm_out``).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .layers import init_linear, qlinear, rms_norm
from .pshard import constrain

__all__ = ["SSMConfig", "init_ssm", "ssd_forward", "ssm_decode_step", "SSMState",
           "init_ssm_state", "ssm_prefill_state", "ssm_recur"]


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    d_conv: int = 4
    n_groups: int = 1
    chunk: int = 256

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim

    def conv_dim(self, d_model: int) -> int:
        return self.d_inner(d_model) + 2 * self.n_groups * self.d_state


def init_ssm(key: jax.Array, d_model: int, cfg: SSMConfig) -> dict:
    ki, ko, kc, kd = jax.random.split(key, 4)
    di = cfg.d_inner(d_model)
    h = cfg.n_heads(d_model)
    cd = cfg.conv_dim(d_model)
    # in_proj → [z (gate), xBC (conv'd), dt] ; out_proj back to d_model
    return {
        "in_proj": init_linear(ki, d_model, 2 * di + 2 * cfg.n_groups * cfg.d_state + h),
        "out_proj": init_linear(ko, di, d_model),
        "conv_w": jax.random.normal(kc, (cfg.d_conv, cd), jnp.float32) / np.sqrt(cfg.d_conv),
        "conv_b": jnp.zeros((cd,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h).astype(jnp.float32)),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((h,), 1e-2, jnp.float32))),  # softplus⁻¹
        "norm_g": jnp.ones((di,), jnp.float32),
    }


def _split_proj(proj: jax.Array, d_model: int, cfg: SSMConfig):
    di = cfg.d_inner(d_model)
    gn = cfg.n_groups * cfg.d_state
    h = cfg.n_heads(d_model)
    z, xbc, dt = jnp.split(proj, [di, di + di + 2 * gn], axis=-1)
    return z, xbc, dt  # xbc = [x, B, C] pre-conv


def _segsum(a: jax.Array) -> jax.Array:
    """Lower-triangular segment sums: out[..., i, j] = Σ_{j<k<=i} a[..., k]."""
    q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, out, -jnp.inf)


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv over time: xbc [B, S, C], w [K, C]."""
    k = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1], :] * w[i] for i in range(k))
    return jax.nn.silu(out + b)


def ssd_forward(params: dict, x: jax.Array, bits_in: jax.Array,
                bits_out: jax.Array, cfg: SSMConfig,
                return_final_state: bool = False,
                unroll: bool = False,
                valid: "jax.Array | None" = None):
    """Chunked SSD over a full sequence. x ``[B, S, d_model]`` → same shape.

    Optionally returns the final recurrent state (for prefill → decode
    handoff): ``(h [B, H, P, N], conv_tail [B, K-1, convdim])``.

    ``valid`` ``[B, S]`` bool marks real tokens of a left-padded ragged batch.
    Pad steps must not touch the recurrence: their inputs are zeroed (so the
    causal conv sees the same implicit zero left-context as an unpadded run,
    and the handed-off ``conv_tail`` pads are exactly zero) and their ``dt`` is
    zero-masked (decay ``exp(0)=1`` → state passthrough, zero input
    contribution) — the same trick the chunk padding below already uses.
    """
    bsz, s_real, d_model = x.shape
    if valid is not None:
        x = jnp.where(valid[..., None], x, 0).astype(x.dtype)
    di = cfg.d_inner(d_model)
    h_heads = cfg.n_heads(d_model)
    p_dim = cfg.head_dim
    n = cfg.d_state
    g = cfg.n_groups
    q = min(cfg.chunk, s_real)
    pad = (-s_real) % q
    s = s_real + pad
    nc = s // q

    proj = qlinear(params["in_proj"], x, bits_in)
    z, xbc, dt = _split_proj(proj, d_model, cfg)
    conv_tail = xbc[:, max(0, s_real - (cfg.d_conv - 1)):s_real, :]
    if s_real < cfg.d_conv - 1:
        # short-prompt prefill: left-pad to the fixed [B, K-1, convdim] window
        # so the handed-off SSMState matches init_ssm_state's aval (decode
        # scans carry the state — shapes must be static across steps)
        conv_tail = jnp.pad(conv_tail,
                            ((0, 0), (cfg.d_conv - 1 - s_real, 0), (0, 0)))
    xbc = _causal_conv(xbc, params["conv_w"], params["conv_b"])
    if pad:  # pad to a chunk multiple; dt is zero-masked there, so the
        # recurrent state passes through padded steps unchanged.
        z = jnp.pad(z, ((0, 0), (0, pad), (0, 0)))
        xbc = jnp.pad(xbc, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    xs, b_, c_ = jnp.split(xbc, [di, di + g * n], axis=-1)

    xh = xs.reshape(bsz, s, h_heads, p_dim).astype(jnp.float32)
    # SSD head counts (24/50) rarely divide the TP axis; shard the head *dim*
    # P instead so the chunk matmuls parallelize (§Perf iteration)
    xh = constrain(xh, "dp", None, None, "tp")
    b_ = b_.reshape(bsz, s, g, n).astype(jnp.float32)
    c_ = c_.reshape(bsz, s, g, n).astype(jnp.float32)
    # broadcast groups → heads
    rep = h_heads // g
    bh = jnp.repeat(b_, rep, axis=2)                     # [B, S, H, N]
    ch = jnp.repeat(c_, rep, axis=2)

    a = -jnp.exp(params["A_log"].astype(jnp.float32))    # [H], negative
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # [B,S,H]
    if valid is not None:  # ragged rows: pad steps pass the state through
        vmask = valid.astype(jnp.float32)
        if pad:
            vmask = jnp.pad(vmask, ((0, 0), (0, pad)))
        dt = dt * vmask[:, :, None]
    elif pad:  # dt→0 on padded steps: decay=exp(0)=1, input contribution 0
        cmask = (jnp.arange(s) < s_real).astype(jnp.float32)[None, :, None]
        dt = dt * cmask
    da = dt * a                                          # [B, S, H]
    xdt = xh * dt[..., None]                             # dt-weighted input

    # chunk
    def chunked(t):  # [B, S, ...] -> [B, nc, Q, ...]
        return t.reshape(bsz, nc, q, *t.shape[2:])
    xc, bc, cc = chunked(xdt), chunked(bh), chunked(ch)
    dac = chunked(da).transpose(0, 3, 1, 2)              # [B, H, nc, Q]

    # intra-chunk (diagonal blocks)
    l_mat = jnp.exp(_segsum(dac))                        # [B, H, nc, Q, Q]
    l_mat = constrain(l_mat, "dp", None, None, "tp", None)
    y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp", cc, bc, l_mat, xc)
    y_diag = constrain(y_diag, "dp", None, "tp", None, None)

    # chunk states and inter-chunk recurrence
    dac_cum = jnp.cumsum(dac, axis=-1)                   # [B, H, nc, Q]
    decay_states = jnp.exp(dac_cum[..., -1:] - dac_cum)  # [B, H, nc, Q]
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", bc, decay_states, xc)
    chunk_decay = jnp.exp(dac_cum[..., -1])              # [B, H, nc]

    def scan_fn(h_prev, inp):
        st, dec = inp                                    # [B,H,P,N], [B,H]
        h_new = h_prev * dec[..., None, None] + st
        return h_new, h_prev

    h0 = jnp.zeros((bsz, h_heads, p_dim, n), jnp.float32)
    h_final, h_prevs = jax.lax.scan(
        scan_fn, h0,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(2, 0, 1)),
        unroll=nc if unroll else 1)
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)           # [B, nc, H, P, N]

    # inter-chunk contribution
    state_decay = jnp.exp(dac_cum)                       # [B, H, nc, Q]
    y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", cc, h_prevs, state_decay)
    y_off = constrain(y_off, "dp", None, "tp", None, None)

    y = (y_diag + y_off).reshape(bsz, s, h_heads, p_dim)
    y = y + xh * params["D"][None, None, :, None]        # skip
    y = y.reshape(bsz, s, di)[:, :s_real]                # trim chunk padding
    y = y * jax.nn.silu(z[:, :s_real].astype(jnp.float32))  # gate
    y = rms_norm({"g": params["norm_g"]}, y)
    out = qlinear(params["out_proj"], y.astype(x.dtype), bits_out)
    if return_final_state:
        return out, (h_final, conv_tail)
    return out


class SSMState(NamedTuple):
    """Decode-time recurrent state: SSD state + causal-conv tail window."""

    h: jax.Array          # [B, H, P, N] f32
    conv: jax.Array       # [B, K-1, convdim]


def init_ssm_state(batch: int, d_model: int, cfg: SSMConfig) -> SSMState:
    return SSMState(
        h=jnp.zeros((batch, cfg.n_heads(d_model), cfg.head_dim, cfg.d_state), jnp.float32),
        conv=jnp.zeros((batch, cfg.d_conv - 1, cfg.conv_dim(d_model)), jnp.float32),
    )


def ssm_prefill_state(final_state, batch, d_model, cfg: SSMConfig) -> SSMState:
    h_final, conv_tail = final_state
    return SSMState(h=h_final, conv=conv_tail.astype(jnp.float32))


def ssm_recur(h: jax.Array, x: jax.Array, dt: jax.Array, a: jax.Array,
              b: jax.Array, c: jax.Array, d: jax.Array):
    """One token's state update, every row: ``h <- h*exp(dt*A) + (dt*x) (x) B``,
    ``y = h.C + D*x``. h ``[B, H, P, N]``, x ``[B, H, P]``, dt ``[B, H]``
    (after softplus), a/d ``[H]``, b/c ``[B, G, N]`` → (y ``[B, H, P]``,
    h). The XLA form: the CPU path and the oracle of the Pallas kernel
    (:mod:`repro.kernels.ssm_decode`)."""
    rep = h.shape[1] // b.shape[1]
    bh = jnp.repeat(b, rep, axis=1).astype(jnp.float32)
    ch = jnp.repeat(c, rep, axis=1).astype(jnp.float32)
    dec = jnp.exp(dt * a)                                 # [B, H]
    h_new = h * dec[..., None, None] + \
        jnp.einsum("bhp,bhn->bhpn", x * dt[..., None], bh)
    y = jnp.einsum("bhpn,bhn->bhp", h_new, ch) + x * d[None, :, None]
    return y, h_new


def ssm_decode_step(params: dict, x: jax.Array, state: SSMState,
                    bits_in: jax.Array, bits_out: jax.Array, cfg: SSMConfig,
                    recur=None):
    """One-token recurrent step. x ``[B, 1, d_model]`` → (y, new_state).

    ``recur`` (default :func:`ssm_recur`) computes the state update from
    ``state.h`` as given, with :func:`ssm_recur`'s other arguments; it
    returns ``(y, h)`` and its ``h`` becomes the new state's. A caller may
    pass a whole stack of layers' states in ``state.h`` with a ``recur``
    that updates one layer of it in place."""
    bsz, _, d_model = x.shape
    di = cfg.d_inner(d_model)
    h_heads = cfg.n_heads(d_model)
    p_dim, n, g = cfg.head_dim, cfg.d_state, cfg.n_groups

    proj = qlinear(params["in_proj"], x, bits_in)[:, 0]   # [B, ...]
    z, xbc, dt = _split_proj(proj, d_model, cfg)

    # conv window update
    window = jnp.concatenate([state.conv, xbc[:, None, :].astype(jnp.float32)], axis=1)
    conv_out = jnp.einsum("bkc,kc->bc", window, params["conv_w"]) + params["conv_b"]
    conv_out = jax.nn.silu(conv_out)
    new_conv = window[:, 1:]

    xs, b_, c_ = jnp.split(conv_out, [di, di + g * n], axis=-1)
    xh = xs.reshape(bsz, h_heads, p_dim).astype(jnp.float32)
    a = -jnp.exp(params["A_log"].astype(jnp.float32))
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # [B, H]
    y, h_new = (recur or ssm_recur)(
        state.h, xh, dt, a, b_.reshape(bsz, g, n).astype(jnp.float32),
        c_.reshape(bsz, g, n).astype(jnp.float32),
        params["D"].astype(jnp.float32))
    y = y.reshape(bsz, di) * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm({"g": params["norm_g"]}, y)
    out = qlinear(params["out_proj"], y[:, None, :].astype(x.dtype), bits_out)
    return out, SSMState(h=h_new, conv=new_conv)
