"""LM-family model assembly: dense / MoE / SSM / hybrid / VLM / audio, and
interleaved stacks whose layers are Mamba-2 or attention by a per-layer
pattern.

One configurable stack covers all ten assigned architectures. Invariants:

* every matmul runs through the quantized path (the paper's technique applies
  uniformly; per-layer precision arrives as the traced ``bits_row`` of the
  adaptive engine);
* layers are stacked and executed with ``lax.scan`` (+ optional remat) so the
  HLO is depth-independent — an 80-layer 110B config lowers as fast as a 2-layer
  smoke config (DESIGN §8.2);
* attention windows and per-layer bit-widths are *data*, so one traced program
  serves every profile of the merged engine.

Public entry points:
  ``init_params``        — parameter pytree (stacked layers)
  ``quant_layer_names``  — names for building profiles / the bits table
  ``forward``            — hidden states over a full sequence (train/prefill)
  ``train_loss``         — chunked-vocab xent + MoE aux losses
  ``init_caches`` / ``decode_step`` / ``prefill`` — serving path
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .attention import (KVCache, PagedKVCache, decode_attention,
                        decode_attention_window, gqa_attention, init_kv_cache,
                        init_paged_kv_cache, kv_refine,
                        paged_decode_attention,
                        paged_decode_attention_window, paged_view,
                        prefix_attention, swa_attention, update_kv_cache,
                        update_kv_cache_window, update_paged_kv_cache,
                        update_paged_kv_cache_window)
from .pshard import constrain
from .layers import (embed_lookup, init_embed, init_linear, init_norm,
                     layer_norm, qlinear, rms_norm)
from .mlp import init_mlp, mlp
from .moe import MoEConfig, init_moe, moe_ffn
from .rotary import apply_mrope, apply_rope, text_mrope_positions
from .ssm import (SSMConfig, SSMState, init_ssm, init_ssm_state,
                  ssd_forward, ssm_decode_step)

__all__ = ["ModelConfig", "init_params", "quant_layer_names", "forward",
           "train_loss", "init_caches", "init_paged_caches", "decode_step",
           "decode_many", "decode_segment", "prefill", "prefill_extend",
           "forward_extend", "cache_bytes", "supports_prefix_sharing",
           "paged_row_masters", "amax_for_scale",
           "prequant_decode_weights", "decode_image", "overlay_params",
           "param_count", "active_param_count"]


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | vlm | audio
    #                                  | interleaved (``layer_types``)
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    rope_theta: float = 1e6
    rope: bool = True                # False: no positional encoding (NoPE)
    qkv_bias: bool = False
    mrope: bool = False
    mrope_sections: tuple[int, ...] = (16, 24, 24)
    sliding_window: int = 0          # 0 = full attention
    causal: bool = True              # False → encoder-only (audio)
    act: str = "silu"
    norm: str = "rms"                # rms | ln
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    frontend: Optional[str] = None   # audio | vision (stub, DESIGN §4)
    n_patches: int = 0               # VLM vision-prefix length
    # interleaved family: each layer's mixer, "mamba" or "attention"; every
    # layer is pre-norm mixer + pre-norm gated MLP, both residual
    layer_types: Optional[tuple[str, ...]] = None
    feature_dim: int = 512           # audio stub frame-embedding dim
    tie_embeddings: bool = False
    remat: bool = True
    loss_chunk: int = 1024           # seq positions per logits chunk
    attn_block_k: int = 512
    # analysis knobs (dry-run roofline extrapolation; DESIGN §7):
    scan_layers: bool = True         # False → python loop (depth-unrolled HLO)
    unroll_inner: bool = False       # unroll attention/SSD/loss scans
    # §Perf hillclimb knobs (defaults = optimized; dryrun flags restore baseline)
    remat_policy: str = "nothing"    # nothing | dots (save matmul outputs)
    swa_block_skip: bool = True      # block-skipping sliding-window attention

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def has_attn(self) -> bool:
        return self.family in ("dense", "moe", "hybrid", "vlm", "audio",
                               "interleaved")

    @property
    def has_ssm(self) -> bool:
        return self.family in ("ssm", "hybrid", "interleaved")

    @property
    def has_mlp(self) -> bool:
        return self.family in ("dense", "hybrid", "vlm", "audio",
                               "interleaved")

    def window(self, skv: int) -> int:
        return self.sliding_window if self.sliding_window else skv + 1


# quantization sites per family (paper granularity: per layer, per block kind)
_SITES = {
    "dense": ("qkv", "attn_out", "mlp_in", "mlp_out"),
    "vlm": ("qkv", "attn_out", "mlp_in", "mlp_out"),
    "audio": ("qkv", "attn_out", "mlp_in", "mlp_out"),
    "moe": ("qkv", "attn_out", "router", "expert_in", "expert_out",
            "shared_in", "shared_out"),
    "ssm": ("ssm_in", "ssm_out"),
    "hybrid": ("qkv", "attn_out", "ssm_in", "ssm_out", "mlp_in", "mlp_out"),
}
# an interleaved stack's layer takes its kind's sites; both kinds have four,
# at the same places, so the bits table stays [L, 4] and a site's index is
# the same in either kind
_KIND_SITES = {"attention": ("qkv", "attn_out", "mlp_in", "mlp_out"),
               "mamba": ("ssm_in", "ssm_out", "mlp_in", "mlp_out")}
_GLOBAL_SITES = ("embed", "lm_head")


def sites(cfg: ModelConfig, layer: int = 0) -> tuple[str, ...]:
    """Quantization sites of ``layer`` (every layer's, in a uniform stack)."""
    if cfg.layer_types:
        return _KIND_SITES[cfg.layer_types[layer]]
    return _SITES[cfg.family]


def layers_of(cfg: ModelConfig, kind: str) -> tuple[int, ...]:
    """Depth indices of the layers holding ``kind``'s state: "attention"
    (KV) or "mamba" (recurrent state). In a uniform stack that is every
    layer or none; Hymba's layers hold both."""
    if cfg.layer_types:
        return tuple(i for i, k in enumerate(cfg.layer_types) if k == kind)
    has = cfg.has_attn if kind == "attention" else cfg.has_ssm
    return tuple(range(cfg.n_layers)) if has else ()


def quant_layer_names(cfg: ModelConfig) -> tuple[str, ...]:
    """Names for Profile construction: globals + per-depth per-site."""
    return _GLOBAL_SITES + tuple(
        f"L{i}.{s}" for i in range(cfg.n_layers) for s in sites(cfg, i))


def split_bits(cfg: ModelConfig, bits_row: jax.Array):
    """bits_row [2 + L*S, 2] → (embed [2], lm_head [2], layers [L, S, 2])."""
    ns = len(sites(cfg))
    return (bits_row[0], bits_row[1],
            bits_row[2:].reshape(cfg.n_layers, ns, 2))


def _site_idx(cfg: ModelConfig, name: str) -> int:
    if cfg.layer_types:
        return next(s.index(name) for s in _KIND_SITES.values() if name in s)
    return sites(cfg).index(name)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(cfg: ModelConfig, key: jax.Array) -> dict:
    ks = jax.random.split(key, 8)
    p: dict[str, Any] = {}
    d, hd = cfg.d_model, cfg.hd
    if cfg.has_attn:
        qkv_out = (cfg.n_heads + 2 * cfg.n_kv) * hd
        p["qkv"] = init_linear(ks[0], d, qkv_out, bias=cfg.qkv_bias)
        p["attn_out"] = init_linear(ks[1], cfg.n_heads * hd, d)
        p["norm_attn"] = init_norm(d, bias=cfg.norm == "ln")
    if cfg.has_ssm:
        p["ssm"] = init_ssm(ks[2], d, cfg.ssm)
        if cfg.family == "ssm":
            p["norm_ssm"] = init_norm(d, bias=False)
    if cfg.family == "hybrid":
        # parallel-head fusion norms (Hymba): per-path output norms
        p["norm_attn_out"] = init_norm(d)
        p["norm_ssm_out"] = init_norm(d)
    if cfg.has_mlp:
        p["mlp"] = init_mlp(ks[3], d, cfg.d_ff, gated=cfg.act == "silu", act=cfg.act)
        p["norm_mlp"] = init_norm(d, bias=cfg.norm == "ln")
    if cfg.family == "moe":
        p["moe"] = init_moe(ks[4], d, cfg.moe)
        p["norm_mlp"] = init_norm(d)
    return p


def _init_kind_layer(cfg: ModelConfig, kind: str, key: jax.Array) -> dict:
    """One layer of an interleaved stack: its mixer (GQA attention or
    Mamba-2) and the gated MLP, each behind its own RMS norm."""
    ks = jax.random.split(key, 3)
    d, hd = cfg.d_model, cfg.hd
    p: dict[str, Any] = {"norm_mlp": init_norm(d),
                         "mlp": init_mlp(ks[0], d, cfg.d_ff)}
    if kind == "attention":
        p["qkv"] = init_linear(ks[1], d, (cfg.n_heads + 2 * cfg.n_kv) * hd,
                               bias=cfg.qkv_bias)
        p["attn_out"] = init_linear(ks[2], cfg.n_heads * hd, d)
        p["norm_attn"] = init_norm(d)
    else:
        p["ssm"] = init_ssm(ks[1], d, cfg.ssm)
        p["norm_ssm"] = init_norm(d)
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    if cfg.layer_types:
        # one stack per kind, in depth order within each: no layer holds
        # weights of the other kind
        layers = {}
        for kind, kk in zip(_KIND_SITES, jax.random.split(k_layers)):
            keys = jax.random.split(kk, len(layers_of(cfg, kind)))
            layers[kind] = jax.vmap(
                partial(_init_kind_layer, cfg, kind))(keys)
    else:
        layer_keys = jax.random.split(k_layers, cfg.n_layers)
        layers = jax.vmap(lambda k: _init_layer(cfg, k))(layer_keys)
    p = {
        "layers": layers,
        "norm_f": init_norm(cfg.d_model, bias=cfg.norm == "ln"),
    }
    if cfg.frontend == "audio":
        p["embed"] = init_linear(k_emb, cfg.feature_dim, cfg.d_model)
    else:
        p["embed"] = init_embed(k_emb, cfg.vocab, cfg.d_model)
    if not cfg.tie_embeddings:
        p["lm_head"] = init_linear(k_head, cfg.d_model, cfg.vocab, scale=0.02)
    return p


def param_count(params) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params))


def active_param_count(cfg: ModelConfig, params) -> int:
    """MoE-aware: routed experts count at top_k/E (for MODEL_FLOPS = 6·N_active·D)."""
    total = param_count(params)
    if cfg.family != "moe":
        return total
    e, k = cfg.moe.n_routed, cfg.moe.top_k
    routed = cfg.n_layers * e * 3 * cfg.moe.d_expert * cfg.d_model
    return total - routed + int(routed * k / e)


# ---------------------------------------------------------------------------
# layer body
# ---------------------------------------------------------------------------

def _norm(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    return layer_norm(p, x) if cfg.norm == "ln" else rms_norm(p, x)


def _attn_qkv(cfg: ModelConfig, lp: dict, x: jax.Array, lb: jax.Array,
              positions: jax.Array):
    """Project + rope. Returns q [B,S,H,hd], k/v [B,S,Hkv,hd]."""
    b, s, _ = x.shape
    hd = cfg.hd
    qkv = qlinear(lp["qkv"], x, lb[_site_idx(cfg, "qkv")])
    q, k, v = jnp.split(
        qkv, [cfg.n_heads * hd, (cfg.n_heads + cfg.n_kv) * hd], axis=-1)
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s, cfg.n_kv, hd)
    v = v.reshape(b, s, cfg.n_kv, hd)
    if cfg.mrope:
        pos3 = text_mrope_positions(positions)
        q = apply_mrope(q, pos3, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, pos3, cfg.mrope_sections, cfg.rope_theta)
    elif cfg.rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    # NB: no head-axis constraint here — attention internals are S-sharded
    # (see gqa_attention); a conflicting H→tp pin forces SPMD full remat.
    return q, k, v


def _attend(cfg: ModelConfig, q, k, v, s: int, kv_valid=None):
    """Dispatch: block-skipping SWA (exact, S·window FLOPs) vs masked blockwise."""
    if (cfg.sliding_window and cfg.causal and cfg.swa_block_skip
            and s > cfg.sliding_window and q.shape[1] == k.shape[1]):
        return swa_attention(q, k, v, window=cfg.sliding_window,
                             block_q=cfg.attn_block_k, kv_valid=kv_valid)
    return gqa_attention(q, k, v, causal=cfg.causal, window=cfg.window(s),
                         block_k=cfg.attn_block_k, unroll=cfg.unroll_inner,
                         kv_valid=kv_valid)


def _layer_forward(cfg: ModelConfig, lp: dict, lb: jax.Array, x: jax.Array,
                   positions: jax.Array, collect_kv: bool,
                   collect_ssm: bool, valid: Optional[jax.Array] = None,
                   kv_eff: Optional[jax.Array] = None):
    """One layer over a full sequence. Returns (x, aux, collected).

    ``valid`` ``[B, S]`` bool marks real tokens of a left-padded ragged batch
    (None = every token real): pad keys are masked out of attention, pad steps
    are masked out of the SSM recurrence, and pad tokens are dropped from the
    MoE capacity dispatch — a ragged row computes exactly what it would solo.
    ``kv_eff`` (traced int32 scalar, optional) is this layer's precision-
    policy bit-width: fresh K/V are refined (:func:`~repro.models.attention.
    kv_refine`) right after the QKV projection, so attention reads AND the
    collected cache/master values see the same refined tensors.
    """
    b, s, d = x.shape
    aux = jnp.zeros((), jnp.float32)
    collected = ()

    if cfg.family == "hybrid":
        xin = _norm(cfg, lp["norm_attn"], x)
        q, k, v = _attn_qkv(cfg, lp, xin, lb, positions)
        if kv_eff is not None:
            k, v = kv_refine(k, kv_eff), kv_refine(v, kv_eff)
        attn = _attend(cfg, q, k, v, s, kv_valid=valid)
        attn = qlinear(lp["attn_out"], attn.reshape(b, s, -1),
                       lb[_site_idx(cfg, "attn_out")])
        ssm_call = partial(ssd_forward, lp["ssm"], xin,
                           lb[_site_idx(cfg, "ssm_in")],
                           lb[_site_idx(cfg, "ssm_out")], cfg.ssm,
                           unroll=cfg.unroll_inner, valid=valid)
        if collect_ssm:
            ssm_out, fin = ssm_call(return_final_state=True)
        else:
            ssm_out, fin = ssm_call(), None
        y = 0.5 * (rms_norm(lp["norm_attn_out"], attn)
                   + rms_norm(lp["norm_ssm_out"], ssm_out))
        x = x + y
        x = x + mlp(lp["mlp"], _norm(cfg, lp["norm_mlp"], x),
                    lb[_site_idx(cfg, "mlp_in")], lb[_site_idx(cfg, "mlp_out")],
                    gated=cfg.act == "silu", act=cfg.act)
        if collect_kv or collect_ssm:
            collected = ((k, v) if collect_kv else None,
                         fin if collect_ssm else None)
        return x, aux, collected

    if cfg.family == "ssm":
        xin = _norm(cfg, lp["norm_ssm"], x)
        call = partial(ssd_forward, lp["ssm"], xin,
                       lb[_site_idx(cfg, "ssm_in")],
                       lb[_site_idx(cfg, "ssm_out")], cfg.ssm,
                       unroll=cfg.unroll_inner, valid=valid)
        if collect_ssm:
            y, fin = call(return_final_state=True)
            collected = (None, fin)
        else:
            y = call()
        return x + y, aux, collected

    # attention families: dense / moe / vlm / audio
    xin = _norm(cfg, lp["norm_attn"], x)
    q, k, v = _attn_qkv(cfg, lp, xin, lb, positions)
    if kv_eff is not None:
        k, v = kv_refine(k, kv_eff), kv_refine(v, kv_eff)
    attn = _attend(cfg, q, k, v, s, kv_valid=valid)
    x = x + qlinear(lp["attn_out"], attn.reshape(b, s, -1),
                    lb[_site_idx(cfg, "attn_out")])
    x = constrain(x, "dp", None, None)
    xm = _norm(cfg, lp["norm_mlp"], x)
    if cfg.family == "moe":
        bits = {name: lb[_site_idx(cfg, name)]
                for name in ("router", "expert_in", "expert_out",
                             "shared_in", "shared_out")}
        y, moe_aux = moe_ffn(lp["moe"], xm, bits, cfg.moe, token_valid=valid)
        aux = aux + moe_aux["load_balance"] + moe_aux["router_z"]
    else:
        y = mlp(lp["mlp"], xm, lb[_site_idx(cfg, "mlp_in")],
                lb[_site_idx(cfg, "mlp_out")],
                gated=cfg.act == "silu", act=cfg.act)
    x = x + y
    if collect_kv:
        collected = ((k, v), None)
    return x, aux, collected


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def _embed_inputs(cfg: ModelConfig, params: dict, bits_row: jax.Array,
                  batch: dict) -> tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """Tokens/features/patches → initial hidden states + positions + validity.

    ``batch["prompt_len"]`` (``[B]`` int32, optional) marks ragged rows that
    were left-padded to a common length: row ``i``'s real tokens occupy the
    *last* ``prompt_len[i]`` columns. Each row then gets per-row position
    offsets (``positions = arange(S) - pad``, so real tokens count 0..len−1
    exactly as they would solo) and a ``valid`` mask over its real tokens; pad
    embeddings are zeroed so pad junk never inflates activation-quant scales.
    Without ``prompt_len`` the behavior (and lowering) is unchanged.
    """
    eb, _, _ = split_bits(cfg, bits_row)
    if cfg.frontend == "audio":
        x = qlinear(params["embed"], batch["features"], eb)
        b, s = x.shape[:2]
    else:
        x = embed_lookup(params["embed"], batch["tokens"], eb)
        b, s = batch["tokens"].shape
        if cfg.frontend == "vision" and cfg.n_patches:
            # vision prefix: precomputed patch embeddings replace the first
            # n_patches positions (frontend stub per the brief)
            patches = batch["patch_embeds"].astype(x.dtype)
            x = jnp.concatenate([patches, x[:, cfg.n_patches:]], axis=1)
    plen = batch.get("prompt_len")
    if plen is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        valid = None
    else:
        pad = s - jnp.asarray(plen, jnp.int32)               # [B] left-pad
        positions = jnp.arange(s, dtype=jnp.int32)[None] - pad[:, None]
        valid = positions >= 0                               # [B, S]
        x = jnp.where(valid[..., None], x, 0).astype(x.dtype)
    return constrain(x, "dp", None, None), positions, valid


def forward(params: dict, cfg: ModelConfig, bits_row: jax.Array, batch: dict,
            collect: bool = False, kv_sched: Optional[jax.Array] = None,
            state_into: Optional[tuple] = None,
            layer_images: Optional[tuple] = None):
    """Backbone over a full sequence.

    Returns (hidden [B,S,d], aux_loss, collected) where ``collected`` stacks
    per-layer (kv, ssm_final) when ``collect`` (prefill → cache handoff).
    ``kv_sched`` (``int32[L]``, optional, *data*) is a per-layer KV
    precision-policy row — each layer's fresh K/V are refined at its entry's
    bit-width before attention/collection; ``None`` keeps the lowering
    byte-identical to the policy-free path (the scan xs tuple is unchanged).
    ``state_into``, ``layer_images`` (interleaved stacks): see
    :func:`_forward_interleaved`.
    """
    if cfg.layer_types:
        return _forward_interleaved(params, cfg, bits_row, batch, collect,
                                    kv_sched, state_into, layer_images)
    x, positions, valid = _embed_inputs(cfg, params, bits_row, batch)
    _, _, layer_bits = split_bits(cfg, bits_row)

    def body(carry, xs):
        x, aux = carry
        if kv_sched is None:
            lp, lb = xs
            ke = None
        else:
            lp, lb, ke = xs
        x, a, col = _layer_forward(cfg, lp, lb, x, positions,
                                   collect_kv=collect and cfg.has_attn,
                                   collect_ssm=collect and cfg.has_ssm,
                                   valid=valid, kv_eff=ke)
        return (x, aux + a), col

    body_fn = body
    if cfg.remat:
        body_fn = jax.checkpoint(body, policy=_remat_policy(cfg))
    carry0 = (x, jnp.zeros((), jnp.float32))
    xs_all = ((params["layers"], layer_bits) if kv_sched is None
              else (params["layers"], layer_bits,
                    jnp.asarray(kv_sched, jnp.int32)))
    if cfg.scan_layers:
        (x, aux), collected = jax.lax.scan(body_fn, carry0, xs_all)
    else:  # depth-unrolled variant (roofline analysis lowering)
        carry = carry0
        cols = []
        for l in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[l], params["layers"])
            xs_l = ((lp, layer_bits[l]) if kv_sched is None
                    else (lp, layer_bits[l],
                          jnp.asarray(kv_sched, jnp.int32)[l]))
            carry, col = body_fn(carry, xs_l)
            cols.append(col)
        (x, aux) = carry
        collected = jax.tree.map(lambda *xs: jnp.stack(xs), *cols) if cols and cols[0] else ()
    x = _norm(cfg, params["norm_f"], x)
    return x, aux, collected


def _remat_policy(cfg: ModelConfig):
    """'nothing' = recompute everything in bwd (min memory, +fwd FLOPs);
    'dots' = save matmul outputs (−recompute FLOPs, +memory) — §Perf knob."""
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable


def _lm_head_params(cfg: ModelConfig, params: dict) -> dict:
    if cfg.tie_embeddings:
        emb = params["embed"]
        if "w" in emb:  # the master, also under a decode image of the table
            return {"w": emb["w"].T}
        from repro.core.quantizers import dequantize  # native deployment
        return {"w": dequantize(emb["wq"], jnp.float32).T}
    return params["lm_head"]


def _logits(cfg: ModelConfig, params: dict, bits_row: jax.Array,
            h: jax.Array) -> jax.Array:
    _, hb, _ = split_bits(cfg, bits_row)
    head = (_image_head(cfg, params) if cfg.layer_types
            else _lm_head_params(cfg, params))
    return qlinear(head, h, hb).astype(jnp.float32)


def chunked_xent(cfg: ModelConfig, params: dict, bits_row: jax.Array,
                 hidden: jax.Array, labels: jax.Array) -> jax.Array:
    """Cross-entropy with seq-chunked logits — the full [B,S,V] tensor never
    materializes (DESIGN §5; V up to 152k makes it ~300 TB otherwise)."""
    b, s, d = hidden.shape
    c = min(cfg.loss_chunk, s)
    assert s % c == 0
    hc = hidden.reshape(b, s // c, c, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, s // c, c).transpose(1, 0, 2)

    def chunk_loss(carry, xs):
        h_, l_ = xs
        logits = constrain(_logits(cfg, params, bits_row, h_),
                           "dp", None, "tp")
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(l_, 0)[..., None], axis=-1)[..., 0]
        mask = (l_ >= 0).astype(jnp.float32)
        nll = (logz - gold) * mask
        tot, cnt = carry
        return (tot + nll.sum(), cnt + mask.sum()), None

    fn = chunk_loss
    if cfg.remat:
        fn = jax.checkpoint(chunk_loss, policy=_remat_policy(cfg))
    (tot, cnt), _ = jax.lax.scan(fn, (jnp.zeros(()), jnp.zeros(())), (hc, lc),
                                 unroll=(s // c) if cfg.unroll_inner else 1)
    return tot / jnp.maximum(cnt, 1.0)


def train_loss(params: dict, cfg: ModelConfig, bits_row: jax.Array,
               batch: dict):
    """Next-token (or frame-classification) loss + MoE aux. Returns (loss, metrics)."""
    hidden, aux, _ = forward(params, cfg, bits_row, batch)
    if cfg.causal:
        labels = batch["labels"]          # already shifted by the data pipeline
    else:
        labels = batch["labels"]          # frame targets (audio)
    loss = chunked_xent(cfg, params, bits_row, hidden, labels)
    total = loss + aux
    return total, {"xent": loss, "aux": aux}


# ---------------------------------------------------------------------------
# serving: caches, prefill, decode
# ---------------------------------------------------------------------------

def _stack_layerwise(fn, n_layers: int):
    """init helper: build per-layer cache pytrees stacked on axis 0."""
    one = fn()
    return jax.tree.map(lambda l: jnp.broadcast_to(l[None], (n_layers, *l.shape)).copy(), one)


def init_caches(cfg: ModelConfig, batch: int, slots: int, *,
                kv_bits: int = 16) -> dict:
    """Decode caches, stacked [L, ...] over the layers that hold each kind
    (:func:`layers_of`). ``slots`` bounds the attention window (SWA archs
    allocate only their window — what makes hymba long_500k O(W))."""
    caches: dict[str, Any] = {}
    if cfg.has_attn:
        eff = min(slots, cfg.sliding_window) if cfg.sliding_window else slots
        dt = jnp.float32 if kv_bits == 32 else jnp.bfloat16
        caches["kv"] = _stack_layerwise(
            lambda: init_kv_cache(batch, eff, cfg.n_kv, cfg.hd, bits=kv_bits,
                                  dtype=dt),
            len(layers_of(cfg, "attention")))
    if cfg.has_ssm:
        caches["ssm"] = _stack_layerwise(
            lambda: init_ssm_state(batch, cfg.d_model, cfg.ssm),
            len(layers_of(cfg, "mamba")))
    return caches


def paged_block_size(cfg: ModelConfig, slots: int, block_size: int) -> int:
    """Largest block size ≤ ``block_size`` compatible with ``cfg``.

    Sliding-window stacks ring-wrap at the window, so exact equivalence
    with the contiguous ring requires the block size to divide the window
    (a non-divisor request degrades to the largest divisor). Full-attention
    stacks never wrap within a valid request — their virtual row just
    rounds up to a whole number of blocks — so any block size works.
    """
    bs = max(1, int(block_size))
    if cfg.sliding_window:
        eff = min(slots, cfg.sliding_window)
        while eff % bs and bs > 1:
            bs -= 1
    return bs


def init_paged_caches(cfg: ModelConfig, batch: int, slots: int, *,
                      kv_bits: int = 16, block_size: int = 16,
                      pool_blocks: Optional[int] = None) -> dict:
    """Paged decode caches: the KV pool is a global set of fixed-size blocks.

    Same contract as :func:`init_caches` (stacked ``[L, ...]``, scanned over
    layers), but attention state is a :class:`repro.models.attention.
    PagedKVCache`: ``pool_blocks`` physical blocks of ``block_size`` tokens
    shared by all ``batch`` rows, each row owning a ``[n_lblk]`` block table
    (``n_lblk = ceil(eff_slots / block_size)``). ``pool_blocks=None``
    provisions ``batch * n_lblk`` — exactly the contiguous footprint; a
    scheduler that shares prefixes or admits short rows can provision far
    less. SSM state is O(1) per row and stays dense, as in
    :func:`init_caches`.
    """
    caches: dict[str, Any] = {}
    if cfg.has_attn:
        eff = min(slots, cfg.sliding_window) if cfg.sliding_window else slots
        bs = paged_block_size(cfg, slots, block_size)
        n_lblk = -(-eff // bs)
        nb = batch * n_lblk if pool_blocks is None else int(pool_blocks)
        dt = jnp.float32 if kv_bits == 32 else jnp.bfloat16
        caches["kv"] = _stack_layerwise(
            lambda: init_paged_kv_cache(batch, nb, bs, n_lblk, cfg.n_kv,
                                        cfg.hd, bits=kv_bits, dtype=dt),
            len(layers_of(cfg, "attention")))
    if cfg.has_ssm:
        caches["ssm"] = _stack_layerwise(
            lambda: init_ssm_state(batch, cfg.d_model, cfg.ssm),
            len(layers_of(cfg, "mamba")))
    return caches


def cache_bytes(caches) -> int:
    """Device bytes held by a cache pytree (KV pools, block tables, scales,
    SSM state) — the serving bench's KV-memory-footprint metric."""
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(caches))


def supports_prefix_sharing(cfg: ModelConfig) -> bool:
    """Whether the shared-prefix admission path is exact for this stack.

    Requires full causal attention with per-position state only: the prefix
    KV is position-addressed, so any row can map it. Sliding-window stacks
    ring-wrap (a shared block would eventually be overwritten), SSM stacks
    carry a recurrent state that is not per-position, and MoE capacity
    dispatch couples tokens across the batch — those families take the cold
    paged path instead (still paged, just no cross-request block mapping).
    """
    return (cfg.has_attn and not cfg.has_ssm and cfg.family != "moe"
            and not cfg.sliding_window and cfg.causal)


def supports_speculation(cfg: ModelConfig, kv_bits: int = 16) -> bool:
    """Whether draft/verify speculative decoding is exact for this stack.

    Same structural requirements as prefix sharing — full causal attention
    with per-position state only. SSM recurrences and MoE capacity dispatch
    couple a window's positions to batch/sequence state a rejected draft
    cannot roll back, and a sliding-window ring could wrap a speculative
    tail onto live slots. Additionally requires kv16/kv8: the int4 packed
    cache has no per-query dequant ladder (see
    ``attention.decode_attention_window``).
    """
    return supports_prefix_sharing(cfg) and kv_bits in (8, 16)


def paged_row_masters(kv_pool, slot: int, block_ids, n_tok: int):
    """Full-precision K/V masters of one paged row's first ``n_tok`` tokens.

    The preemption snapshot: gathers the row's mapped pool blocks
    (``block_ids``, logical order — shared CoW prefix blocks included) and
    returns ``(mk, mv)`` as ``[L, n_tok, Hkv, hd]`` float32, dequantized
    under the row's *current* per-``[L, Hkv]`` scales. For int KV that is
    not the historically-written value of early tokens (the running-max
    scale moved after they were quantized) — it is exactly the value whose
    re-quantization under the same scale reproduces the stored ints
    bit-for-bit, which is the roundtrip :meth:`ContinuousScheduler.
    evict_row`/resume needs: replaying these masters through the
    continuation-prefill executable rebuilds the row's cache state
    byte-identically. Runs eagerly on the host side between segments (a
    handful of gathers per eviction — preemption is the exceptional path);
    row *eviction* itself needs no dispatch beyond the existing
    fixed-shape table clear, exactly like in-graph retirement.
    """
    from .attention import _dequantize_kv
    bs = kv_pool.k.shape[2]                  # [L, n_blocks, bs, Hkv, hd]
    nb = -(-n_tok // bs)
    bids = jnp.asarray(np.asarray(list(block_ids)[:nb], np.int32)
                       .reshape(nb))

    def gather(pool, scale):
        g = jnp.take(pool, bids, axis=1)     # [L, nb, bs, Hkv, hd']
        g = g.reshape(g.shape[0], nb * bs, *g.shape[3:])[:, :n_tok]
        if kv_pool.bits in (4, 8):
            return _dequantize_kv(g, scale, kv_pool.bits)
        return g.astype(jnp.float32)

    return (gather(kv_pool.k, kv_pool.k_scale[:, slot]),
            gather(kv_pool.v, kv_pool.v_scale[:, slot]))


def amax_for_scale(scale: np.ndarray, qmax: float,
                   strict: bool = True) -> np.ndarray:
    """Invert the int-KV scale calibration ``s = amax/qmax + 1e-9``, f32-exact.

    The preemption restore wave re-quantizes a suspended row's masters
    through ``prefill_extend``'s calibration ``max(suffix_amax, amax)/qmax
    + 1e-9``; passing an ``amax`` whose forward image is bit-equal to the
    row's suspended scale makes the restored scale — and with it every
    re-quantized int — identical to the uninterrupted row's. Scales born
    of true f32 division have such a preimage within a few ulp of
    ``(s − 1e-9)·qmax``; this searches per element. But XLA may lower a
    divide-by-constant as multiply-by-reciprocal (observed inside the
    fused decode scan at qmax=7), and division by a non-power-of-2 maps
    the float grid ~1.14 result-ulps per input ulp — so a device-produced
    scale can sit on a result value that true division skips entirely, at
    ANY search radius. ``strict=False`` returns the nearest approximate
    preimage for such elements instead of raising; callers relying on
    bit-exact restoration must then force the exact scale separately
    (``RowSnapshot.k_scale``/``v_scale`` — re-quantization itself is
    robust to a few-ulp scale error since ``round(i·(1±ε)) == i`` for
    ``|i| ≤ qmax``, so only the scale bytes need forcing).
    """
    s = np.asarray(scale, np.float32)
    qmax32, eps = np.float32(qmax), np.float32(1e-9)

    def fwd(a):
        return np.float32(np.float32(a / qmax32) + eps)

    out = np.empty_like(s)
    it = np.nditer(s, flags=["multi_index"])
    for sv in it:
        sv = np.float32(sv)
        a = np.float32(np.float32(sv - eps) * qmax32)
        lo = hi = a
        for _ in range(64):
            if fwd(a) == sv:
                break
            hi = np.nextafter(hi, np.float32(np.inf), dtype=np.float32)
            if fwd(hi) == sv:
                a = hi
                break
            lo = np.nextafter(lo, np.float32(-np.inf), dtype=np.float32)
            if fwd(lo) == sv:
                a = lo
                break
        else:
            if strict:
                raise ValueError(f"no amax preimage for scale {sv!r}")
            a = np.float32(np.float32(sv - eps) * qmax32)
        out[it.multi_index] = a
    return out


def _attn_decode(cfg: ModelConfig, lp: dict, lb: jax.Array, xin: jax.Array,
                 positions: jax.Array, pos: jax.Array, cache: dict,
                 paged_backend: str, ke: Optional[jax.Array]):
    """The attention half of one layer's decode step: QKV of the normed
    ``xin [B, 1, d]`` at ``positions [B, 1]``, the cache write, attention
    and the output projection. ``cache`` holds the layer's ``"kv"`` (and
    the segment's ``"kv_view"``); returns ``(attn [B, 1, d], the updated
    entries)``."""
    b = xin.shape[0]
    q, k, v = _attn_qkv(cfg, lp, xin, lb, positions)
    if ke is not None:
        k, v = kv_refine(k, ke), kv_refine(v, ke)
    new = {}
    if "kv_view" in cache:
        # paged fast path (decode_segment): the block table is
        # fixed for the whole segment, so the dense per-row view
        # was gathered ONCE at segment entry, rides the carry, and
        # takes every read AND write of the segment — exactly the
        # contiguous ring's per-step cost. The pool passes through
        # untouched; decode_segment folds the view's blocks back
        # through the block tables once, at segment exit.
        kvc = cache["kv"]
        view = update_kv_cache(cache["kv_view"], k, v, pos)
        attn = decode_attention(
            q, view, pos,
            window=cfg.window(view.token_idx.shape[1]))
        new["kv_view"] = view
    elif isinstance(cache["kv"], PagedKVCache):
        kvc = update_paged_kv_cache(cache["kv"], k, v, pos)
        slots_p = kvc.block_table.shape[1] * kvc.k.shape[1]
        if paged_backend == "pallas":
            # in-place path: the kernel streams mapped pool blocks
            # through the block table; no dense view is built
            attn = paged_decode_attention(
                q, kvc, pos, window=cfg.window(slots_p))
        else:
            # standalone paged step: gather the view on the spot
            view = paged_view(kvc)
            attn = decode_attention(
                q, view, pos, window=cfg.window(slots_p))
    else:
        kvc = update_kv_cache(cache["kv"], k, v, pos)
        attn = decode_attention(
            q, kvc, pos,
            window=cfg.window(kvc.token_idx.shape[1]))
    attn = qlinear(lp["attn_out"], attn.reshape(b, 1, -1),
                   lb[_site_idx(cfg, "attn_out")])
    new["kv"] = kvc
    return attn, new


def decode_step(params: dict, cfg: ModelConfig, bits_row: jax.Array,
                tokens: jax.Array, pos: jax.Array, caches: dict,
                row_valid: Optional[jax.Array] = None,
                paged_backend: str = "gather",
                kv_sched: Optional[jax.Array] = None,
                layer_images: Optional[tuple] = None):
    """One decode step. tokens ``[B,1]``, pos ``[B]`` → (logits [B,V], caches).

    ``row_valid`` ``[B]`` bool marks rows still generating (continuous-batching
    slot pools carry retired/free rows): dead rows are dropped from the MoE
    capacity dispatch so they cannot displace a live row's expert routing.
    Non-MoE families ignore it (batch rows are independent there).

    ``paged_backend`` (static) picks how a :class:`PagedKVCache` is read:
    ``"gather"`` materializes the dense per-row view (:func:`paged_view`, the
    CPU/oracle path) while ``"pallas"`` attends **in place** against the
    block pool (:func:`repro.models.attention.paged_decode_attention`) — no
    ``[B, n_lblk*bs]`` copy exists anywhere in the step.

    ``kv_sched`` (``int32[L]``, optional, *data*): per-layer precision-policy
    row — the step's fresh K/V are refined per layer before the cache write
    and the attention read, exactly like the prefill paths.

    ``layer_images`` ``(images, img)`` (from :func:`_image_split`): the
    layers' stacked weight images (``[n_images, L, ...]``) and the (traced)
    image to use. Each layer grafts its own slice ``a[img, l]`` onto its
    weights inside the layer loop, so the stack is a loop invariant and no
    copy of a whole image is made. ``None``: the layers read ``params``.
    """
    if cfg.layer_types:
        return _decode_interleaved(params, cfg, bits_row, tokens, pos,
                                   caches, paged_backend, kv_sched,
                                   layer_images=layer_images)
    eb, _, layer_bits = split_bits(cfg, bits_row)
    x = embed_lookup(params["embed"], tokens, eb)
    positions = pos[:, None].astype(jnp.int32)
    b = tokens.shape[0]

    def body(x, xs):
        lp, lb, cache, ke, l = xs
        if l is not None:
            lp = _graft_layer(lp, layer_images, l)
        new_cache = dict(cache)
        if cfg.has_attn:
            xin = _norm(cfg, lp["norm_attn"], x)
            attn, upd = _attn_decode(cfg, lp, lb, xin, positions, pos,
                                     cache, paged_backend, ke)
            new_cache.update(upd)
        if cfg.family == "hybrid":
            ssm_out, st = ssm_decode_step(lp["ssm"], xin, cache["ssm"],
                                          lb[_site_idx(cfg, "ssm_in")],
                                          lb[_site_idx(cfg, "ssm_out")], cfg.ssm)
            y = 0.5 * (rms_norm(lp["norm_attn_out"], attn)
                       + rms_norm(lp["norm_ssm_out"], ssm_out))
            x = x + y
            x = x + mlp(lp["mlp"], _norm(cfg, lp["norm_mlp"], x),
                        lb[_site_idx(cfg, "mlp_in")],
                        lb[_site_idx(cfg, "mlp_out")])
            new_cache["ssm"] = st
        elif cfg.family == "ssm":
            xin = _norm(cfg, lp["norm_ssm"], x)
            y, st = ssm_decode_step(lp["ssm"], xin, cache["ssm"],
                                    lb[_site_idx(cfg, "ssm_in")],
                                    lb[_site_idx(cfg, "ssm_out")], cfg.ssm)
            x = x + y
            new_cache["ssm"] = st
        else:
            x = x + attn
            xm = _norm(cfg, lp["norm_mlp"], x)
            if cfg.family == "moe":
                bits = {name: lb[_site_idx(cfg, name)]
                        for name in ("router", "expert_in", "expert_out",
                                     "shared_in", "shared_out")}
                y, _ = moe_ffn(lp["moe"], xm, bits,
                               dataclasses.replace(
                                   cfg.moe, groups=math.gcd(cfg.moe.groups, b)),
                               token_valid=(None if row_valid is None
                                            else row_valid[:, None]))
                x = x + y
            else:
                x = x + mlp(lp["mlp"], xm, lb[_site_idx(cfg, "mlp_in")],
                            lb[_site_idx(cfg, "mlp_out")],
                            gated=cfg.act == "silu", act=cfg.act)
        return x, new_cache

    layers_and_caches = (
        params["layers"], layer_bits, caches,
        None if kv_sched is None else jnp.asarray(kv_sched, jnp.int32),
        None if layer_images is None else jnp.arange(cfg.n_layers,
                                                     dtype=jnp.int32))
    if cfg.scan_layers:
        x, new_caches = jax.lax.scan(body, x, layers_and_caches)
    else:  # depth-unrolled analysis variant
        new_list = []
        for l in range(cfg.n_layers):
            xs_l = jax.tree.map(lambda a: a[l], layers_and_caches)
            x, nc_ = body(x, xs_l)
            new_list.append(nc_)
        new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *new_list)
    x = _norm(cfg, params["norm_f"], x)
    logits = _logits(cfg, params, bits_row, x)[:, 0]
    return logits, new_caches


# ---------------------------------------------------------------------------
# interleaved stacks: each layer Mamba-2 + MLP or attention + MLP
# ---------------------------------------------------------------------------

def _period_plan(cfg: ModelConfig):
    """The pattern as ``periods`` repeats of its shortest period, each kind's
    layers per period, and the period as runs of like layers ``(kind,
    first, n)`` (``first`` counts the period's earlier layers of the kind)."""
    kinds = tuple(cfg.layer_types)
    n = len(kinds)
    p = next(p for p in range(1, n + 1)
             if n % p == 0 and kinds == kinds[:p] * (n // p))
    runs, per = [], dict.fromkeys(_KIND_SITES, 0)
    for kind, grp in itertools.groupby(kinds[:p]):
        k = len(list(grp))
        runs.append((kind, per[kind], k))
        per[kind] += k
    return n // p, per, runs


def _over_layers(cfg: ModelConfig, layer, carry):
    """``carry = layer(kind, j, carry)`` over the stack in depth order, ``j``
    the layer's (traced) index among its kind's. A scan over the pattern's
    periods holds one scan per run of the period, so the program has one
    body per run whatever the depth."""
    n_per, per, runs = _period_plan(cfg)

    def period(carry, k):
        for kind, first, n in runs:
            carry, _ = jax.lax.scan(
                lambda c, j, kind=kind: (layer(kind, j, c), None), carry,
                k * per[kind] + first + jnp.arange(n, dtype=jnp.int32))
        return carry, None

    return jax.lax.scan(period, carry, jnp.arange(n_per, dtype=jnp.int32))[0]


def _by_kind(cfg: ModelConfig, rows: jax.Array) -> dict:
    """Per-layer rows ``[L, ...]`` split into each kind's, in depth order."""
    return {kind: rows[np.asarray(layers_of(cfg, kind), np.int32)]
            for kind in _KIND_SITES}


def _at(tree, j):
    return jax.tree.map(lambda a: a[j], tree)


def _mlp_half(cfg: ModelConfig, lp: dict, lb: jax.Array, x: jax.Array):
    return x + mlp(lp["mlp"], _norm(cfg, lp["norm_mlp"], x),
                   lb[_site_idx(cfg, "mlp_in")], lb[_site_idx(cfg, "mlp_out")],
                   gated=cfg.act == "silu", act=cfg.act)


def _forward_interleaved(params: dict, cfg: ModelConfig, bits_row: jax.Array,
                         batch: dict, collect: bool,
                         kv_sched: Optional[jax.Array],
                         state_into: Optional[tuple] = None,
                         layer_images: Optional[tuple] = None):
    """:func:`forward` of an interleaved stack. ``collect`` writes each
    attention layer's K/V and each Mamba layer's final state and conv tail
    into buffers the layers carry, stacked by kind (``[L_attn, ...]``,
    ``[L_ssm, ...]``). ``state_into`` ``(state, rows)``: the states go into
    rows ``rows`` (out of range: dropped) of ``state``, a pool's stacked
    :class:`SSMState`, layer by layer — no ``[L_ssm, B, ...]`` buffer of
    the batch's states is made. ``layer_images``: as
    :func:`_decode_interleaved` takes them."""
    x, positions, valid = _embed_inputs(cfg, params, bits_row, batch)
    _, _, layer_bits = split_bits(cfg, bits_row)
    lbits = _by_kind(cfg, layer_bits)
    kes = (None if kv_sched is None else
           _by_kind(cfg, jnp.asarray(kv_sched, jnp.int32))["attention"])
    b, s, d = x.shape
    col = {}
    if collect:
        sc = cfg.ssm
        kv = jnp.zeros((len(layers_of(cfg, "attention")), b, s, cfg.n_kv,
                        cfg.hd), x.dtype)
        lm = len(layers_of(cfg, "mamba"))
        if state_into is None:
            col = {"h": jnp.zeros((lm, b, sc.n_heads(d), sc.head_dim,
                                   sc.d_state), jnp.float32),
                   "conv": jnp.zeros((lm, b, sc.d_conv - 1, sc.conv_dim(d)),
                                     jnp.float32)}
        else:
            col = {"h": state_into[0].h, "conv": state_into[0].conv}
        col |= {"k": kv, "v": kv}
    rows = slice(None) if state_into is None else state_into[1]

    def layer(kind, j, carry):
        x, col = carry
        lp = _layer_weights(params, kind, j, layer_images)
        lb = lbits[kind][j]
        col = dict(col)
        if kind == "attention":
            xin = _norm(cfg, lp["norm_attn"], x)
            q, k, v = _attn_qkv(cfg, lp, xin, lb, positions)
            if kes is not None:
                k, v = kv_refine(k, kes[j]), kv_refine(v, kes[j])
            attn = _attend(cfg, q, k, v, s, kv_valid=valid)
            x = x + qlinear(lp["attn_out"], attn.reshape(b, s, -1),
                            lb[_site_idx(cfg, "attn_out")])
            if collect:
                col["k"], col["v"] = col["k"].at[j].set(k), \
                    col["v"].at[j].set(v)
        else:
            out = ssd_forward(lp["ssm"], _norm(cfg, lp["norm_ssm"], x),
                              lb[_site_idx(cfg, "ssm_in")],
                              lb[_site_idx(cfg, "ssm_out")], cfg.ssm,
                              return_final_state=collect,
                              unroll=cfg.unroll_inner, valid=valid)
            if collect:
                out, (h, tail) = out
                col["h"] = col["h"].at[j, rows].set(h, mode="drop")
                col["conv"] = col["conv"].at[j, rows].set(
                    tail.astype(jnp.float32), mode="drop")
            x = x + out
        return _mlp_half(cfg, lp, lb, x), col

    fn = layer
    if cfg.remat:
        def fn(kind, j, carry):
            return jax.checkpoint(partial(layer, kind),
                                  policy=_remat_policy(cfg))(j, carry)
    x, col = _over_layers(cfg, fn, (x, col))
    x = _norm(cfg, params["norm_f"], x)
    collected = (((col["k"], col["v"]), (col["h"], col["conv"]))
                 if collect else ())
    return x, jnp.zeros((), jnp.float32), collected


def _image_split(params: dict, prequant: dict, pid: jax.Array):
    """The weights under profile ``pid``'s image: the params with the global
    images (embedding, head) grafted on, and ``(layer images, img)`` for the
    layer loop (``None`` where no layer has an image), where each layer
    takes its own slice — a copy of the whole image per step costs a read
    and a write of every layer's weights, and at full width would not fit
    beside an interleaved stack's recurrent state."""
    img = prequant["image_of"][pid]
    images = dict(prequant["images"])
    lay = images.pop("layers", None)
    return (overlay_params(params, _at(images, img)),
            (lay, img) if lay else None)


def _graft_layer(lp: dict, layer_images: tuple, j) -> dict:
    """Layer ``j``'s weights ``lp`` with its slice of the image
    ``layer_images = (images [n_images, L, ...], img)`` grafted on."""
    images, img = layer_images
    return overlay_params(lp, jax.tree.map(lambda a: a[img, j], images))


def _layer_weights(params: dict, kind: str, j, layer_images):
    lp = _at(params["layers"][kind], j)
    if layer_images is not None and kind in layer_images[0]:
        lp = _graft_layer(lp, (layer_images[0][kind], layer_images[1]), j)
    return lp


def _decode_interleaved(params: dict, cfg: ModelConfig, bits_row: jax.Array,
                        tokens: jax.Array, pos: jax.Array, caches: dict,
                        paged_backend: str, kv_sched: Optional[jax.Array],
                        layer_images: Optional[tuple] = None):
    """:func:`decode_step` of an interleaved stack. The caches ride the
    layer loop's carry whole — KV ``[L_attn, ...]``, recurrent state
    ``[L_ssm, ...]`` — and each layer updates its own entry. With
    ``paged_backend="pallas"`` the state update is the Pallas kernel, which
    writes the layer's state in place inside the stacked buffer.
    ``layer_images`` ``(images, img)``: the layers' stacked weight images
    (``[n_images, L_kind, ...]`` per kind) and the (traced) image to use;
    each layer grafts its own slice onto its weights."""
    eb, _, layer_bits = split_bits(cfg, bits_row)
    x = embed_lookup(params["embed"], tokens, eb)
    positions = pos[:, None].astype(jnp.int32)
    lbits = _by_kind(cfg, layer_bits)
    kes = (None if kv_sched is None else
           _by_kind(cfg, jnp.asarray(kv_sched, jnp.int32))["attention"])

    def layer(kind, j, carry):
        x, cch = carry
        lp = _layer_weights(params, kind, j, layer_images)
        lb = lbits[kind][j]
        cch = dict(cch)
        if kind == "attention":
            xin = _norm(cfg, lp["norm_attn"], x)
            attn, upd = _attn_decode(
                cfg, lp, lb, xin, positions, pos,
                {k: _at(cch[k], j) for k in ("kv", "kv_view") if k in cch},
                paged_backend, None if kes is None else kes[j])
            for k, new in upd.items():
                cch[k] = jax.tree.map(lambda a, u: a.at[j].set(u), cch[k],
                                      new)
            x = x + attn
        else:
            st = cch["ssm"]
            xin = _norm(cfg, lp["norm_ssm"], x)
            bits = (lb[_site_idx(cfg, "ssm_in")], lb[_site_idx(cfg, "ssm_out")])
            if paged_backend == "pallas":
                y, new = ssm_decode_step(
                    lp["ssm"], xin, SSMState(st.h, st.conv[j]), *bits,
                    cfg.ssm, recur=partial(_ssm_kernel_recur, j))
                h = new.h
            else:
                y, new = ssm_decode_step(
                    lp["ssm"], xin, SSMState(st.h[j], st.conv[j]), *bits,
                    cfg.ssm)
                h = st.h.at[j].set(new.h)
            cch["ssm"] = SSMState(h=h, conv=st.conv.at[j].set(new.conv))
            x = x + y
        return _mlp_half(cfg, lp, lb, x), cch

    x, caches = _over_layers(cfg, layer, (x, dict(caches)))
    x = _norm(cfg, params["norm_f"], x)
    return _logits(cfg, params, bits_row, x)[:, 0], caches


def _image_head(cfg: ModelConfig, params: dict) -> dict:
    """The output head an interleaved stack multiplies by: under a weight
    image, the image — the head's own, else (tied, same bits) the
    embedding's transposed — so no step fake-quants the f32 master table;
    without one, :func:`_lm_head_params`."""
    emb = params["embed"]
    if "lm_head" in params:
        return params["lm_head"]
    if not cfg.tie_embeddings:
        return _lm_head_params(cfg, params)
    if "wq" in emb:
        return {"wq": emb["wq"]._replace(data=emb["wq"].data.T)}
    if "wfq" in emb:
        return {"wfq": emb["wfq"].T}
    return _lm_head_params(cfg, params)


def _ssm_kernel_recur(layer, h, x, dt, a, b, c, d):
    """:func:`~repro.models.ssm.ssm_recur`'s contract over the stacked state
    ``h [L_ssm, ...]``, updating layer ``layer`` in place through the Pallas
    kernel (interpret mode off the TPU)."""
    from repro.kernels.ssm_decode import ssm_decode_pallas
    return ssm_decode_pallas(h, layer, x, dt, a, b, c, d,
                             interpret=jax.default_backend() != "tpu")


def prequant_decode_weights(params: dict, cfg: ModelConfig,
                            table: jax.Array) -> dict:
    """Hoist weight fake-quant out of the decode loop.

    The seed decode path re-fake-quanted every weight matrix on *every step*
    — pure overhead around the approximate kernels. Since weights are
    step-invariant, quantize them once up front, one image per **distinct
    weight-width row** of the (concrete) ``[P, L, 2]`` bits table: profiles
    that differ only in activation bits share an image (the six paper
    profiles need two, W8 and W4). Returns ``{"image_of": int32[P],
    "images": overlay}`` where the overlay is a sparse pytree parallel to
    ``params`` with a leading image dim. :func:`decode_segment` selects a
    profile's image inside the traced decode step without copying it out of
    the stack: :func:`_image_split` grafts the global images (embedding,
    head) on, and each layer grafts its own slice ``[img, l]`` inside the
    layer loop; the layers' images are stacked ``[n_images, L, ...]`` for
    that (per kind in an interleaved stack).

    A site that every image quantizes at ``<= 8`` bits is held as an int8
    carrier plus its power-of-two scale (``wq`` — a :class:`QTensor`, which
    ``qlinear``/``embed_lookup`` consume on their native branch); its
    dequantized values equal the fake-quant exactly, so tokens do not
    change. Any other site keeps a float fake-quant image (``wfq``). Images
    are built one layer at a time (``lax.map``), so the float temporaries
    stay at one layer's size.

    Sites not covered (MoE routed-expert stacks, tied lm_head) keep the
    in-loop path — fake-quant is idempotent on its own po2 grid, so numerics
    match either way. Native (``wq``) layouts pass through untouched.
    """
    from .layers import SIGNED_SYM
    from repro.core.quantizers import fake_quant_dynamic, po2_carrier

    w_rows, image_of = np.unique(np.asarray(table)[..., 1], axis=0,
                                 return_inverse=True)
    ns = len(sites(cfg))
    layer_rows = w_rows[:, 2:].reshape(len(w_rows), cfg.n_layers, ns)

    def quant(w, wb, narrow, transposed=False):
        if narrow and transposed:
            return {"wqT": po2_carrier(w.T, wb)}   # per-tensor grid: same
        if narrow:
            return {"wq": po2_carrier(w, wb)}
        return {"wfq": fake_quant_dynamic(w, wb, SIGNED_SYM)}

    def one_image(w_row):
        layer_bits = w_row[2:].reshape(cfg.n_layers, ns)

        def glob(w, i):
            return quant(w, w_row[i], bool((w_rows[:, i] <= 8).all()))

        def layer_images(lp, idx):        # a stack of the layers ``idx``
            def stacked(w, name):         # w [len(idx), ...], per-layer bits
                j = _site_idx(cfg, name)
                narrow = bool((layer_rows[:, idx, j] <= 8).all())
                # an interleaved stack's layer loops nest (periods, runs):
                # there the TPU relayouts a whole image stack whose output
                # width is no multiple of 128 unless it is stored [out, in]
                tr = bool(cfg.layer_types) and w.shape[-1] % 128 != 0
                return jax.lax.map(lambda a: quant(a[0], a[1], narrow, tr),
                                   (w, layer_bits[idx, j]))

            lov: dict[str, Any] = {}
            if "w" in lp.get("qkv", {}):
                lov["qkv"] = stacked(lp["qkv"]["w"], "qkv")
                lov["attn_out"] = stacked(lp["attn_out"]["w"], "attn_out")
            if "mlp" in lp and "w" in lp["mlp"]["w_in"]:
                lov["mlp"] = {
                    "w_in": stacked(lp["mlp"]["w_in"]["w"], "mlp_in"),
                    "w_out": stacked(lp["mlp"]["w_out"]["w"], "mlp_out")}
            if "ssm" in lp and "w" in lp["ssm"]["in_proj"]:
                lov["ssm"] = {
                    "in_proj": stacked(lp["ssm"]["in_proj"]["w"], "ssm_in"),
                    "out_proj": stacked(lp["ssm"]["out_proj"]["w"],
                                        "ssm_out")}
            if "moe" in lp and "w" in lp["moe"]["router"]:
                moev: dict[str, Any] = {
                    "router": stacked(lp["moe"]["router"]["w"], "router")}
                if "shared_in" in lp["moe"]:
                    moev["shared_in"] = stacked(lp["moe"]["shared_in"]["w"],
                                                "shared_in")
                    moev["shared_out"] = stacked(
                        lp["moe"]["shared_out"]["w"], "shared_out")
                lov["moe"] = moev
            return lov

        ov: dict[str, Any] = {}
        if "w" in params["embed"] and cfg.frontend != "audio":
            ov["embed"] = glob(params["embed"]["w"], 0)
        if not cfg.tie_embeddings and "w" in params.get("lm_head", {}):
            ov["lm_head"] = glob(params["lm_head"]["w"], 1)
        elif (cfg.layer_types and "w" in params["embed"]
              and (w_rows[:, 0] != w_rows[:, 1]).any()):
            # an interleaved stack's tied head reads the embedding's image
            # (:func:`_image_head`); it has its own only where a profile
            # gives the two other bits
            ov["lm_head"] = glob(params["embed"]["w"].T, 1)
        if cfg.layer_types:
            lov = {kind: layer_images(params["layers"][kind],
                                      np.asarray(layers_of(cfg, kind)))
                   for kind in _KIND_SITES}
            lov = {k: v for k, v in lov.items() if v}
        else:
            lov = layer_images(params["layers"], slice(None))
        if lov:
            ov["layers"] = lov
        return ov

    return {"image_of": jnp.asarray(image_of.reshape(-1), jnp.int32),
            "images": jax.vmap(one_image)(jnp.asarray(w_rows, jnp.int32))}


def decode_image(prequant: dict, pid: jax.Array) -> dict:
    """Profile ``pid``'s whole weight image (traced ``pid``) from
    :func:`prequant_decode_weights`. Under a traced ``pid`` this copies
    every layer's image out of the stack, so the decode segment selects per
    layer instead (:func:`_image_split`); the speculative segment
    (:func:`decode_segment_spec`) still takes the whole image."""
    img = prequant["image_of"][pid]
    return jax.tree.map(lambda a: a[img], prequant["images"])


def overlay_params(base: dict, overlay: dict) -> dict:
    """Graft a (sliced) prequant overlay onto the base params pytree. ``wfq``
    leaves land next to the float masters; the quantized consumers prefer
    them, and the untouched ``w`` twins are dead-code-eliminated from the
    compiled scan. The decode segment grafts the global images onto the
    whole params and each layer's image slice onto that layer's weights
    inside the layer loop (:func:`_graft_layer`)."""
    out = dict(base)
    for k, v in overlay.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            out[k] = overlay_params(base[k], v)
        else:
            out[k] = v
    return out


def decode_many(params: dict, cfg: ModelConfig, table: jax.Array,
                schedule: jax.Array, logits0: jax.Array, pos0: jax.Array,
                caches: dict, row_budget: Optional[jax.Array] = None,
                prequant: Optional[dict] = None,
                kv_table: Optional[jax.Array] = None):
    """Fused multi-token greedy decode: one ``lax.scan`` over generation steps.

    The whole decode loop stays on device — per-step argmax sampling, KV/SSM
    cache updates, and profile switching all happen inside a single scan, so a
    generate call is one dispatch instead of one per token.

    * ``table`` ``[P, L, 2]`` — the merged engine's bits table; the active
      profile per step is ``schedule[i]`` (``int32[steps]``, *data*: a new
      schedule never retraces — the paper's runtime configuration word).
    * ``logits0`` ``[B, V]`` — prefill logits; ``tokens[:, 0]`` is their argmax
      (the profile that produced them is ``schedule[0]``).
    * ``pos0`` ``[B]`` — absolute position of the first decode step (prompt
      length for left-padded batches).
    * ``caches`` — decode caches from :func:`prefill`; threaded through the
      scan carry (donate them at the ``jit`` boundary for in-place updates).
    * ``row_budget`` ``[B]`` — optional per-row token budget (early stop):
      tokens at index ≥ budget are emitted as −1 and frozen rows feed a
      constant 0 token (their junk never reaches live rows — batch rows are
      independent).
    * ``prequant`` — per-profile weight images from
      :func:`prequant_decode_weights`; pass them in when params/table are
      fixed across calls (a server computes them once), else they are built
      here per call.

    Returns ``(tokens [B, steps] int32, pids [steps] int32, caches)`` where
    ``pids`` is the realized per-step profile trace for accounting.
    """
    steps = schedule.shape[0]
    b = logits0.shape[0]
    budget = (jnp.full((b,), steps, jnp.int32) if row_budget is None
              else jnp.asarray(row_budget, jnp.int32))
    tok0 = jnp.argmax(logits0, axis=-1).astype(jnp.int32)
    live0 = 0 < budget
    out0 = jnp.where(live0, tok0, -1)
    # weight images per profile: caller-supplied (once per server) or built
    # once per call — never once per token
    if prequant is None:
        prequant = prequant_decode_weights(params, cfg, table)
    ys, _, _, _, caches = decode_segment(params, cfg, table, schedule[1:],
                                         jnp.where(live0, tok0, 0), pos0,
                                         caches, budget - 1, prequant=prequant,
                                         kv_table=kv_table)
    tokens = jnp.concatenate([out0[:, None], ys], axis=1)
    return tokens, schedule, caches


def decode_segment(params: dict, cfg: ModelConfig, table: jax.Array,
                   schedule: jax.Array, tok0: jax.Array, pos0: jax.Array,
                   caches: dict, remaining: jax.Array,
                   prequant: Optional[dict] = None,
                   paged_backend: str = "gather",
                   fault_step: Optional[jax.Array] = None,
                   kv_table: Optional[jax.Array] = None):
    """Fused decode *segment*: ``len(schedule)`` scan steps from an arbitrary
    mid-generation state — the continuous-batching quantum primitive.

    Unlike :func:`decode_many` there is no prefill-logits prologue: the carry
    enters with ``tok0 [B]`` (each row's last emitted token; 0 for idle slots),
    ``pos0 [B]`` (next absolute position per row), and ``remaining [B]`` (tokens
    each row still has to emit; 0 = retired/free slot). Rows whose ``remaining``
    runs out mid-segment freeze exactly like :func:`decode_many`'s done-mask:
    their outputs come back −1, they feed a constant 0, and (for MoE) they are
    dropped from the expert-capacity dispatch via ``row_valid``. All shapes are
    static in ``(B, len(schedule))``, so a slot-pool server runs every segment
    through ONE compiled executable regardless of which rows are live.

    Paged pools run one of two backends (``paged_backend``, static):

    * ``"gather"`` — the dense per-row view is gathered ONCE at segment
      entry, every step reads/writes the view, and the view's blocks fold
      back through the tables at exit. Exactly the contiguous per-step cost,
      but the segment moves two extra pool-sized copies — the CPU oracle
      path.
    * ``"pallas"`` — every step attends **in place** against the pool
      through the Pallas paged-attention kernel and writes through the block
      table; no ``[B, n_lblk*bs]`` view and no exit fold-back exist in the
      executable. The pool is the single KV residence of the segment.

    Robustness hooks (both data — the pool-lifetime single executable holds):

    * ``fault_step`` ``[B]`` int32 — per-row scan step at which the row's
      logits are replaced with NaN (−1 / out of range = never). This is the
      deterministic fault-injection operand of the serving runtime's chaos
      machinery (:mod:`repro.serving.faults`): it poisons the *logits* only,
      after the KV write, so the pool is never corrupted — exactly the
      failure mode a numerically degraded low-bit profile produces.
    * the returned ``row_ok`` ``[B]`` bool is a per-row finite-check over
      every live step's logits, folded into the scan carry — detection of
      non-finite output (injected or genuine) costs no extra dispatch and
      rides back with the segment's tokens.

    Returns ``(tokens [B, steps], row_ok [B], tok [B], pos [B], caches)`` —
    tok/pos/caches are the carry for the next segment.
    """
    if prequant is None:
        prequant = prequant_decode_weights(params, cfg, table)
    rem = jnp.asarray(remaining, jnp.int32)
    fs = (jnp.full(jnp.shape(tok0), -1, jnp.int32) if fault_step is None
          else jnp.asarray(fault_step, jnp.int32))
    paged = isinstance(caches.get("kv"), PagedKVCache)
    use_kernel = paged and paged_backend == "pallas"
    if paged and not use_kernel:
        # block tables are fixed for the segment: gather the dense per-row
        # view once here instead of once per step inside the scan — the
        # steps read AND write only the view (the pool passes through the
        # scan untouched and absorbs the view's blocks at segment exit)
        caches = dict(caches)
        caches["kv_view"] = jax.vmap(paged_view)(caches["kv"])

    def step(carry, xs):
        pid, i = xs
        tok, pos, ok, cch = carry
        live = i < rem                       # done-mask: row still generating?
        bits_row = table[pid]
        # per-layer KV precision row, gathered by the step's (traced)
        # profile id — like bits_row, a schedule switch never retraces
        ks = None if kv_table is None else kv_table[pid]
        # the global images are grafted here; each layer grafts its own
        # slice of the layer images inside the layer loop
        p_step, lay = _image_split(params, prequant, pid)
        logits, cch = decode_step(p_step, cfg, bits_row, tok[:, None], pos,
                                  cch, row_valid=live,
                                  paged_backend=paged_backend, kv_sched=ks,
                                  layer_images=lay)
        # fault injection: the targeted row's logits go NaN at its fault
        # step — after the KV write (the pool stays clean), before the
        # argmax and finite-check (both token and flag see the poison)
        logits = jnp.where((i == fs)[:, None],
                           jnp.asarray(jnp.nan, logits.dtype), logits)
        # per-row finite-check, folded into the carry: a live row whose
        # logits go non-finite (injected or genuine) drops its ok bit for
        # the rest of the segment; frozen rows never count
        ok = ok & (jnp.all(jnp.isfinite(logits), axis=-1) | ~live)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = jnp.where(live, nxt, -1)
        feed = jnp.where(live, nxt, 0)
        # dead rows freeze their position: their junk writes stay parked on
        # one slot past their last real token instead of marching around the
        # ring — with a paged cache a marching dead row would eventually wrap
        # into its first logical block, which may be a *shared* prefix block
        return (feed, pos + live.astype(jnp.int32), ok, cch), out

    steps = schedule.shape[0]
    carry0 = (jnp.asarray(tok0, jnp.int32), pos0.astype(jnp.int32),
              jnp.ones(jnp.shape(tok0), bool), caches)
    (tok, pos, row_ok, caches), ys = jax.lax.scan(
        step, carry0, (schedule, jnp.arange(steps, dtype=jnp.int32)))
    if use_kernel:
        # no fold-back: every decode write already landed in the pool through
        # the block table. Only the retirement contract remains — rows that
        # FINISH inside this segment come back with their tables unmapped
        # (their cache has no future reader; residual dead-row writes then
        # drop instead of following the freed blocks to their next owner)
        finish = (rem > 0) & (rem <= steps)
        kv = caches["kv"]
        nb = kv.k.shape[1]                       # [L, n_blocks, bs, ...]
        caches = dict(caches)
        caches["kv"] = kv._replace(
            block_table=jnp.where(finish[None, :, None], nb, kv.block_table))
    elif paged:
        # fold the segment's decode writes back into the persistent pool:
        # one blocked scatter per layer instead of one per step. Shared
        # prefix blocks appear in several rows' tables, but decode never
        # writes their virtual range, so every duplicate scatter carries
        # the same original bytes; unmapped tables (free/retired rows)
        # drop, so their junk follows no block to its next owner. Rows that
        # FINISH inside this segment (0 < remaining <= steps) come back
        # unmapped too — their cache has no future reader, so retirement
        # needs no separate table-clearing dispatch from the host.
        caches = dict(caches)
        view = caches.pop("kv_view")
        finish = (rem > 0) & (rem <= steps)

        def writeback(pool_l, view_l):
            b, nlb = pool_l.block_table.shape
            bs = pool_l.k.shape[1]
            nb = pool_l.k.shape[0]
            bt = jnp.where(finish[:, None], nb, pool_l.block_table)
            # scatter is slow on CPU backends, so write back via the INVERSE
            # map instead: one tiny scatter builds pool-block → view-block
            # (shared blocks appear under several rows — any winner carries
            # identical bytes, since decode never writes the shared range),
            # then fast gathers pull each mapped block's new content and a
            # select keeps unmapped blocks' old bytes
            inv = jnp.full((nb + 1,), b * nlb, jnp.int32)
            inv = inv.at[bt.reshape(-1)].set(
                jnp.arange(b * nlb, dtype=jnp.int32), mode="drop")[:nb]
            mapped = inv < b * nlb

            def put(pl, vl):
                blk = vl.reshape(b * nlb, bs, *vl.shape[2:])
                g = jnp.take(blk, inv, axis=0, mode="fill", fill_value=0)
                keep = mapped.reshape((nb,) + (1,) * (g.ndim - 1))
                return jnp.where(keep, g, pl)

            return pool_l._replace(
                k=put(pool_l.k, view_l.k), v=put(pool_l.v, view_l.v),
                token_idx=put(pool_l.token_idx, view_l.token_idx),
                k_scale=view_l.k_scale, v_scale=view_l.v_scale,
                block_table=bt)

        caches["kv"] = jax.vmap(writeback)(caches["kv"], view)
    return ys.T, row_ok, tok, pos, caches


def ngram_propose(hist: jax.Array, tok: jax.Array, k: int,
                  vocab: int) -> jax.Array:
    """Self-speculative n-gram drafter: longest-suffix match (prompt
    lookup) over the row's own history.

    ``hist [B, Hn]`` holds each row's most recent tokens (−1 = empty pad,
    pads only ever on the left), with the *current* token as the last
    entry; ``tok [B]`` is that current token. Each row scores every
    earlier position ``j`` by how long a suffix of the current context it
    matches (up to a trigram, most-recent position winning ties) and
    proposes the ``k`` tokens that followed the best match — periodically
    extended when the match sits closer than ``k`` to the end, so a
    period-``p`` cycle (including alternating-branch cycles a follower
    vote cannot disambiguate) is predicted exactly once one full period
    is in the window. Rows with no match (fresh history) fall back to
    repeating the current token. Pure jnp — runs inside the segment
    scan, zero host round-trips. Returns proposals ``[B, k]`` int32.
    """
    b, hn = hist.shape
    if not k:
        return jnp.zeros((b, 0), jnp.int32)
    h = jnp.asarray(hist, jnp.int32)
    cur = jnp.asarray(tok, jnp.int32)
    depth = min(3, hn - 1)
    # candidate match ends j ∈ [0, hn-2] (j == hn-1 is the trivial
    # self-match); score = longest matching suffix, weighted so a
    # (d+1)-gram match always beats any d-gram match
    j_idx = jnp.arange(hn - 1, dtype=jnp.int32)[None]         # [1, hn-1]
    score = jnp.zeros((b, hn - 1), jnp.int32)
    run = jnp.ones((b, hn - 1), bool)
    for d in range(depth):
        tgt = h[:, hn - 1 - d][:, None]                       # suffix token
        cand = jnp.where(j_idx - d >= 0,
                         jnp.take_along_axis(
                             h, jnp.maximum(j_idx - d, 0), axis=1), -2)
        run = run & (cand == tgt) & (tgt >= 0)
        score = score + (1 << d) * run.astype(jnp.int32)
    best_j = jnp.argmax(score * hn + j_idx, axis=1).astype(jnp.int32)
    matched = jnp.max(score, axis=1) > 0
    # propose the followers of the match; a match p positions from the
    # end extends periodically (idx wraps back by the period), so short
    # cycles draft past their own tail instead of clamping
    period = jnp.maximum(hn - 1 - best_j, 1)
    offs = jnp.arange(k, dtype=jnp.int32)[None]               # [1, k]
    idx = best_j[:, None] + 1 + jnp.mod(offs, period[:, None])
    prop = jnp.take_along_axis(h, jnp.minimum(idx, hn - 1), axis=1)
    prop = jnp.where(matched[:, None] & (prop >= 0), prop, cur[:, None])
    return prop


def decode_step_spec(params: dict, cfg: ModelConfig, bits_row: jax.Array,
                     tokens: jax.Array, pos: jax.Array, caches: dict,
                     row_valid: Optional[jax.Array] = None,
                     paged_backend: str = "gather"):
    """W-token draft/verify forward. tokens ``[B, W]`` (position of
    ``tokens[:, j]`` is ``pos + j``) → ``(logits [B, W, V], caches,
    (k_ladders, v_ladders))`` with ladders ``[L, B, W, Hkv]``.

    The W-wide twin of :func:`decode_step`, restricted to the stacks
    :func:`supports_speculation` admits (dense full-causal attention — no
    SSM/MoE/SWA branches). All W positions are written to the cache before
    attention runs (write-before-read: each query's causal mask only ever
    sees this window's own prefix plus committed history), and the cache's
    *committed* int8 scales are left untouched — the caller commits the
    returned per-position scale ladders at the accepted count once the
    verify pass has resolved (see :func:`decode_segment_spec`).
    """
    eb, _, layer_bits = split_bits(cfg, bits_row)
    x = embed_lookup(params["embed"], tokens, eb)
    b, w = tokens.shape
    positions = (pos[:, None]
                 + jnp.arange(w, dtype=jnp.int32)[None]).astype(jnp.int32)

    def body(x, xs):
        lp, lb, cache = xs
        new_cache = dict(cache)
        xin = _norm(cfg, lp["norm_attn"], x)
        q, k, v = _attn_qkv(cfg, lp, xin, lb, positions)
        if "kv_view" in cache:
            # paged gather path: same segment-lifetime dense view contract
            # as decode_step — the pool passes through untouched and the
            # view's blocks fold back at segment exit
            kvc = cache["kv"]
            view, klad, vlad = update_kv_cache_window(
                cache["kv_view"], k, v, pos)
            attn = decode_attention_window(
                q, view, pos, klad, vlad,
                window=cfg.window(view.token_idx.shape[1]))
            new_cache["kv_view"] = view
        elif isinstance(cache["kv"], PagedKVCache):
            kvc, klad, vlad = update_paged_kv_cache_window(
                cache["kv"], k, v, pos)
            slots_p = kvc.block_table.shape[1] * kvc.k.shape[1]
            if paged_backend == "pallas":
                attn = paged_decode_attention_window(
                    q, kvc, pos, klad, vlad, window=cfg.window(slots_p))
            else:
                view = paged_view(kvc)
                attn = decode_attention_window(
                    q, view, pos, klad, vlad, window=cfg.window(slots_p))
        else:
            kvc, klad, vlad = update_kv_cache_window(cache["kv"], k, v, pos)
            attn = decode_attention_window(
                q, kvc, pos, klad, vlad,
                window=cfg.window(kvc.token_idx.shape[1]))
        attn = qlinear(lp["attn_out"], attn.reshape(b, w, -1),
                       lb[_site_idx(cfg, "attn_out")])
        new_cache["kv"] = kvc
        x = x + attn
        xm = _norm(cfg, lp["norm_mlp"], x)
        x = x + mlp(lp["mlp"], xm, lb[_site_idx(cfg, "mlp_in")],
                    lb[_site_idx(cfg, "mlp_out")],
                    gated=cfg.act == "silu", act=cfg.act)
        return x, (new_cache, (klad, vlad))

    layers_and_caches = (params["layers"], layer_bits, caches)
    if cfg.scan_layers:
        x, (new_caches, ladders) = jax.lax.scan(body, x, layers_and_caches)
    else:  # depth-unrolled analysis variant
        new_list, lad_list = [], []
        for l in range(cfg.n_layers):
            xs_l = jax.tree.map(lambda a: a[l], layers_and_caches)
            x, (nc_, lad_) = body(x, xs_l)
            new_list.append(nc_)
            lad_list.append(lad_)
        new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *new_list)
        ladders = jax.tree.map(lambda *xs: jnp.stack(xs), *lad_list)
    x = _norm(cfg, params["norm_f"], x)
    logits = _logits(cfg, params, bits_row, x)          # [B, W, V]
    return logits, new_caches, ladders


def _commit_window_scales(kv, k_ladders, v_ladders, m: jax.Array, w: int):
    """Commit the scale-ladder entry of the last delivered position.

    ``kv`` is a per-layer-stacked (Paged)KVCache with ``k_scale [L, B,
    Hkv]``; ``k_ladders [L, B, W, Hkv]``; ``m [B]`` delivered counts.
    Rows with ``m == 0`` (frozen) keep their committed scale — a dead
    row's junk amax must never move the scale its historical ints were
    written under.
    """
    if kv.bits != 8:
        return kv
    idx = jnp.clip(m - 1, 0, w - 1).astype(jnp.int32)

    def take(lad):
        sel = jnp.take_along_axis(
            lad, jnp.broadcast_to(idx[None, :, None, None],
                                  lad.shape[:2] + (1,) + lad.shape[3:]),
            axis=2)[:, :, 0]
        return sel

    keep = (m >= 1)[None, :, None]
    return kv._replace(
        k_scale=jnp.where(keep, take(k_ladders), kv.k_scale),
        v_scale=jnp.where(keep, take(v_ladders), kv.v_scale))


def decode_segment_spec(params: dict, cfg: ModelConfig, table: jax.Array,
                        schedule: jax.Array, tok0: jax.Array,
                        pos0: jax.Array, caches: dict, remaining: jax.Array,
                        quota: Optional[jax.Array] = None,
                        hist0: Optional[jax.Array] = None,
                        spec_on: Optional[jax.Array] = None,
                        prequant: Optional[dict] = None,
                        paged_backend: str = "gather",
                        fault_step: Optional[jax.Array] = None,
                        draft_k: int = 4,
                        draft_override: Optional[jax.Array] = None,
                        draft_fn=None):
    """Speculative decode segment: ``len(schedule)`` draft/verify windows.

    Each scan iteration proposes ``draft_k`` tokens per row (self-
    speculative :func:`ngram_propose` by default, or ``draft_fn(hist, tok)
    -> [B, draft_k]`` — e.g. a small-model drafter), feeds the
    ``W = draft_k + 1`` window ``[tok, d_1..d_k]`` through ONE batched
    verify forward (:func:`decode_step_spec`), and advances each row by
    its **delivered** count ``m = min(accepted + 1, remaining, quota)``:
    the greedy argmax chain ``g`` matches the drafts position-wise, the
    accepted count is the length of the matching prefix, and position
    ``accepted`` contributes the free bonus token — so every delivered
    token is exactly the token greedy stepwise decode would emit
    (token-identity by induction). Rejected tail positions are rolled
    back **without host sync**: their cache slots hold junk that the next
    window's write span always overwrites before any query can attend to
    it, and their quantization amaxes never reach the committed int8
    scale (:func:`_commit_window_scales`).

    Mirrors :func:`decode_segment`'s carry/exit contract, with two
    generalizations: the done-mask becomes the per-row delivered count
    ``m ∈ [0, W]``, and ``quota [B]`` bounds the segment's delivered
    tokens per row (the scheduler's quantum measured in *accepted*
    tokens). ``spec_on [B]`` disables speculation per row (``m`` clamps
    to 1 — per-class opt-out). ``fault_step [B]`` poisons the whole
    verify-window logits ``[W, V]`` of the targeted row at the given
    iteration; ``row_ok`` finite-checks all ``W·V`` verify logits of
    every live iteration. ``draft_override [B, n_iter, draft_k]``
    (entries ≥ 0) forces proposals — the acceptance-boundary and
    property-test hook.

    Returns ``(tokens [B, n_iter, W], delivered [B, n_iter], row_ok,
    tok, pos, caches)``; delivered tokens of iteration ``i`` are
    ``tokens[:, i, :delivered[:, i]]``, the rest is −1 padding.
    """
    if prequant is None:
        prequant = prequant_decode_weights(params, cfg, table)
    n_iter = schedule.shape[0]
    b = jnp.shape(tok0)[0]
    w = draft_k + 1
    rem = jnp.asarray(remaining, jnp.int32)
    qta = (jnp.full((b,), jnp.iinfo(jnp.int32).max // 2, jnp.int32)
           if quota is None else jnp.asarray(quota, jnp.int32))
    son = (jnp.ones((b,), bool) if spec_on is None
           else jnp.asarray(spec_on, bool))
    fs = (jnp.full((b,), -1, jnp.int32) if fault_step is None
          else jnp.asarray(fault_step, jnp.int32))
    if hist0 is None:
        hist0 = jnp.full((b, 32), -1, jnp.int32)
        hist0 = hist0.at[:, -1].set(jnp.asarray(tok0, jnp.int32))
    dov = (jnp.full((n_iter, b, draft_k), -1, jnp.int32)
           if draft_override is None
           else jnp.asarray(draft_override, jnp.int32).transpose(1, 0, 2))
    paged = isinstance(caches.get("kv"), PagedKVCache)
    use_kernel = paged and paged_backend == "pallas"
    if paged and not use_kernel:
        caches = dict(caches)
        caches["kv_view"] = jax.vmap(paged_view)(caches["kv"])
    wj = jnp.arange(w, dtype=jnp.int32)[None]

    def _commit_caches(cch, klads, vlads, m):
        cch = dict(cch)
        if "kv_view" in cch:
            cch["kv_view"] = _commit_window_scales(
                cch["kv_view"], klads, vlads, m, w)
        else:
            cch["kv"] = _commit_window_scales(cch["kv"], klads, vlads, m, w)
        return cch

    def step(carry, xs):
        pid, it, dov_i = xs
        tok, pos, rem, qta, ok, hist, cch = carry
        live = (rem > 0) & (qta > 0)
        bits_row = table[pid]
        p_step = overlay_params(params, decode_image(prequant, pid))
        if draft_fn is not None:
            prop = jnp.asarray(draft_fn(hist, tok), jnp.int32)
        else:
            prop = ngram_propose(hist, tok, draft_k, cfg.vocab)
        prop = jnp.where(dov_i >= 0, dov_i, prop)
        feed = jnp.concatenate([tok[:, None], prop], axis=1)     # [B, W]
        feed = jnp.where(live[:, None], feed, 0)
        logits, cch, (klads, vlads) = decode_step_spec(
            p_step, cfg, bits_row, feed, pos, cch, row_valid=live,
            paged_backend=paged_backend)
        # fault injection poisons the whole verify window's logits — after
        # the KV writes (the pool stays clean), before acceptance/argmax
        logits = jnp.where((it == fs)[:, None, None],
                           jnp.asarray(jnp.nan, logits.dtype), logits)
        ok = ok & (jnp.all(jnp.isfinite(logits), axis=(1, 2)) | ~live)
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)        # [B, W]
        if draft_k:
            match = (prop == g[:, :draft_k]).astype(jnp.int32)
            acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
        else:
            acc = jnp.zeros_like(rem)
        m = jnp.where(son,
                      jnp.minimum(jnp.minimum(acc + 1, rem), qta),
                      jnp.minimum(jnp.minimum(1, rem), qta))
        m = jnp.where(live, m, 0).astype(jnp.int32)
        cch = _commit_caches(cch, klads, vlads, m)
        out = jnp.where(wj < m[:, None], g, -1)
        tok = jnp.where(m >= 1,
                        jnp.take_along_axis(
                            g, jnp.clip(m - 1, 0, w - 1)[:, None],
                            axis=1)[:, 0],
                        tok)
        # slide the n-gram history window past the delivered tokens: junk
        # beyond m never enters (the take window ends at the m-th append)
        hcat = jnp.concatenate([hist, g], axis=1)
        idx = m[:, None] + jnp.arange(hist.shape[1], dtype=jnp.int32)[None]
        hist = jnp.take_along_axis(hcat, idx, axis=1)
        return (tok, pos + m, rem - m, qta - m, ok, hist, cch), (out, m)

    carry0 = (jnp.asarray(tok0, jnp.int32), pos0.astype(jnp.int32),
              rem, qta, jnp.ones((b,), bool),
              jnp.asarray(hist0, jnp.int32), caches)
    (tok, pos, rem_out, _, row_ok, _, caches), (ys, ms) = jax.lax.scan(
        step, carry0,
        (schedule, jnp.arange(n_iter, dtype=jnp.int32), dov))
    # retirement contract: rows that finished inside this segment come back
    # with their tables unmapped — delivered counts are data, so `finish`
    # is data too (vs decode_segment's static-step form), but the unmap
    # select is the same fixed-shape op either way
    finish = (rem > 0) & (rem_out <= 0)
    if use_kernel:
        kv = caches["kv"]
        nb = kv.k.shape[1]                       # [L, n_blocks, bs, ...]
        caches = dict(caches)
        caches["kv"] = kv._replace(
            block_table=jnp.where(finish[None, :, None], nb, kv.block_table))
    elif paged:
        caches = dict(caches)
        view = caches.pop("kv_view")

        def writeback(pool_l, view_l):
            b_, nlb = pool_l.block_table.shape
            bs = pool_l.k.shape[1]
            nb = pool_l.k.shape[0]
            bt = jnp.where(finish[:, None], nb, pool_l.block_table)
            inv = jnp.full((nb + 1,), b_ * nlb, jnp.int32)
            inv = inv.at[bt.reshape(-1)].set(
                jnp.arange(b_ * nlb, dtype=jnp.int32), mode="drop")[:nb]
            mapped = inv < b_ * nlb

            def put(pl, vl):
                blk = vl.reshape(b_ * nlb, bs, *vl.shape[2:])
                g = jnp.take(blk, inv, axis=0, mode="fill", fill_value=0)
                keep = mapped.reshape((nb,) + (1,) * (g.ndim - 1))
                return jnp.where(keep, g, pl)

            return pool_l._replace(
                k=put(pool_l.k, view_l.k), v=put(pool_l.v, view_l.v),
                token_idx=put(pool_l.token_idx, view_l.token_idx),
                k_scale=view_l.k_scale, v_scale=view_l.v_scale,
                block_table=bt)

        caches["kv"] = jax.vmap(writeback)(caches["kv"], view)
    return (ys.transpose(1, 0, 2), ms.T, row_ok, tok, pos, caches)


def prefill(params: dict, cfg: ModelConfig, bits_row: jax.Array, batch: dict,
            slots: int, *, kv_bits: int = 16, return_raw_kv: bool = False,
            kv_sched: Optional[jax.Array] = None,
            ssm_into: Optional[tuple] = None,
            images: Optional[tuple] = None):
    """Full-sequence prefill → (last-token logits [B,V], decode-ready caches).

    Ragged batches (``batch["prompt_len"]``): each left-padded row hands off
    its KV entries at per-row *logical* positions (``token_idx = idx − pad``),
    so decode continues at ``pos0 = prompt_len`` exactly where a solo run
    would. Pad slots are never written — their ``token_idx`` stays at the −1
    sentinel, which :func:`repro.models.attention.decode_attention` skips —
    and int-cache dequant scales are calibrated over real tokens only.

    ``return_raw_kv`` additionally returns the *pre-quantization* collected
    per-layer K/V (``(k, v)`` each ``[L, B, S, Hkv, hd]``, still in padded
    column coordinates) as a third result — the full-precision masters a
    prefix registry snapshots so later shared-prefix admissions can replay
    the exact cache-fill (attention reads and int-KV scale calibration) a
    cold prefill would have done.

    ``ssm_into`` ``(state, rows)``: the recurrent state is returned as
    ``state`` (a pool's, stacked ``[L_ssm, n_slots, ...]``) with the batch's
    final states written at pool rows ``rows`` (out of range: dropped) — in
    an interleaved stack layer by layer, with no ``[L_ssm, B, ...]`` buffer
    of the batch's states beside the pool.

    ``images`` ``(prequant, pid)``: :func:`prequant_decode_weights`'s images
    and the profile. An interleaved stack prefills from the profile's image
    (the values fake-quanting its masters gives), each layer taking its own
    slice, and never fake-quants a master; other stacks read their masters.
    """
    layer_images = None
    if images is not None and cfg.layer_types:
        params, layer_images = _image_split(params, *images)
    hidden, _, collected = forward(params, cfg, bits_row, batch, collect=True,
                                   kv_sched=kv_sched,
                                   state_into=(ssm_into if cfg.layer_types
                                               else None),
                                   layer_images=layer_images)
    b, s, _ = hidden.shape
    plen = batch.get("prompt_len")
    caches = init_caches(cfg, b, slots, kv_bits=kv_bits)
    kv_col, ssm_col = (collected if isinstance(collected, tuple) and collected
                       else (None, None))
    if cfg.has_attn and kv_col is not None:
        k_all, v_all = kv_col                   # [L, B, S, Hkv, hd]
        eff = caches["kv"].token_idx.shape[-1]
        take = min(eff, s)
        idx = jnp.arange(s - take, s, dtype=jnp.int32)
        if plen is None:
            slot = idx % eff                    # [take], shared across rows
            tok_w = jnp.broadcast_to(idx[None], (b, take))
            ridx = slice(None)                  # kvc.k.at[:, slot]
            amask = None
        else:
            pad = s - jnp.asarray(plen, jnp.int32)          # [B]
            pos_t = idx[None, :] - pad[:, None]             # [B, take] logical
            real = pos_t >= 0
            slot = jnp.where(real, pos_t % eff, eff)        # OOB slot → drop
            tok_w = jnp.where(real, pos_t, -1)
            ridx = jnp.arange(b)[:, None]       # kvc.k.at[bidx, slot]
            amask = (jnp.arange(s, dtype=jnp.int32)[None] >= pad[:, None])

        def fill(kvc, k_l, v_l):
            if kvc.bits in (4, 8):
                from repro.models.attention import _quantize_kv
                qmax = 127.0 if kvc.bits == 8 else 7.0
                ka = jnp.abs(k_l.astype(jnp.float32))
                va = jnp.abs(v_l.astype(jnp.float32))
                if amask is not None:           # pad junk must not set scales
                    ka = jnp.where(amask[:, :, None, None], ka, 0.0)
                    va = jnp.where(amask[:, :, None, None], va, 0.0)
                ks = jnp.max(ka, axis=(1, 3)) / qmax + 1e-9
                vs = jnp.max(va, axis=(1, 3)) / qmax + 1e-9
                kq = _quantize_kv(k_l, ks, kvc.bits)
                vq = _quantize_kv(v_l, vs, kvc.bits)
            else:
                ks, vs = kvc.k_scale, kvc.v_scale
                kq, vq = k_l.astype(kvc.k.dtype), v_l.astype(kvc.v.dtype)
            return KVCache(
                k=kvc.k.at[ridx, slot].set(kq[:, idx], mode="drop"),
                v=kvc.v.at[ridx, slot].set(vq[:, idx], mode="drop"),
                k_scale=ks, v_scale=vs,
                token_idx=kvc.token_idx.at[ridx, slot].set(tok_w, mode="drop"),
                bits=kvc.bits,
            )

        caches["kv"] = jax.vmap(fill)(caches["kv"], k_all, v_all)
    if cfg.has_ssm and ssm_col is not None:
        h_fin, conv_tail = ssm_col              # [L, B, H, P, N], [L, B, K-1, cd]
        caches["ssm"] = SSMState(h=h_fin, conv=conv_tail.astype(jnp.float32))
        if ssm_into is not None and not cfg.layer_types:
            caches["ssm"] = jax.tree.map(
                lambda pool, row: pool.at[:, ssm_into[1]].set(row,
                                                               mode="drop"),
                ssm_into[0], caches["ssm"])
    logits = _logits(cfg, params, bits_row, hidden[:, -1:])[:, 0]
    if return_raw_kv:
        return logits, caches, kv_col
    return logits, caches


# ---------------------------------------------------------------------------
# shared-prefix continuation prefill (paged KV serving)
# ---------------------------------------------------------------------------

def forward_extend(params: dict, cfg: ModelConfig, bits_row: jax.Array,
                   batch: dict, prefix_k: jax.Array, prefix_v: jax.Array,
                   prefix_len: jax.Array,
                   kv_sched: Optional[jax.Array] = None):
    """Backbone over a prompt *suffix*, attending to precomputed prefix KV.

    The shared-prefix admission path skips re-running the backbone over a
    prefix whose per-layer KV already exists; only the divergent suffix is
    embedded and pushed through the layers, with every attention read
    spanning ``[prefix KV ++ suffix KV]`` (:func:`repro.models.attention.
    prefix_attention`). Positions are absolute (``prefix_len + local``), so
    rope and causal masks line up with what a cold full-prompt prefill
    computes.

    ``batch``: ``tokens [B, Sb]`` left-padded suffixes + ``prompt_len [B]``
    = per-row *suffix* lengths. ``prefix_k``/``prefix_v``: ``[L, B, Pp, Hkv,
    hd]`` full-precision prefix masters, zero-padded past ``prefix_len[row]``
    (their logical positions are ``0..prefix_len−1`` by the shared-prefix
    invariant). Returns ``(hidden [B, Sb, d], (k, v) [L, B, Sb, Hkv, hd])``.
    Only stacks where :func:`supports_prefix_sharing` holds may call this.
    """
    assert supports_prefix_sharing(cfg), cfg.family
    eb, _, layer_bits = split_bits(cfg, bits_row)
    x = embed_lookup(params["embed"], batch["tokens"], eb)
    b, s = batch["tokens"].shape
    slen = jnp.asarray(batch["prompt_len"], jnp.int32)
    plen = jnp.asarray(prefix_len, jnp.int32)
    local = jnp.arange(s, dtype=jnp.int32)[None] - (s - slen)[:, None]
    positions = local + plen[:, None]         # absolute; negative on pads
    valid = local >= 0
    x = jnp.where(valid[..., None], x, 0).astype(x.dtype)
    x = constrain(x, "dp", None, None)

    def body(x, xs):
        if kv_sched is None:
            lp, lb, kp, vp = xs
            ke = None
        else:
            lp, lb, kp, vp, ke = xs
        xin = _norm(cfg, lp["norm_attn"], x)
        q, k, v = _attn_qkv(cfg, lp, xin, lb, positions)
        if ke is not None:
            # refine ONLY the fresh suffix K/V — the prefix masters were
            # refined when they were born; re-refining is not bit-stable
            # (the recomputed fake-quant scale drifts by ulps)
            k, v = kv_refine(k, ke), kv_refine(v, ke)
        attn = prefix_attention(q, kp, vp, k, v, positions=positions,
                                prefix_len=plen, suffix_valid=valid)
        x = x + qlinear(lp["attn_out"], attn.reshape(b, s, -1),
                        lb[_site_idx(cfg, "attn_out")])
        x = constrain(x, "dp", None, None)
        xm = _norm(cfg, lp["norm_mlp"], x)
        x = x + mlp(lp["mlp"], xm, lb[_site_idx(cfg, "mlp_in")],
                    lb[_site_idx(cfg, "mlp_out")],
                    gated=cfg.act == "silu", act=cfg.act)
        return x, (k, v)

    xs_all = ((params["layers"], layer_bits, prefix_k, prefix_v)
              if kv_sched is None
              else (params["layers"], layer_bits, prefix_k, prefix_v,
                    jnp.asarray(kv_sched, jnp.int32)))
    x, kv_col = jax.lax.scan(body, x, xs_all)
    x = _norm(cfg, params["norm_f"], x)
    return x, kv_col


def prefill_extend(params: dict, cfg: ModelConfig, bits_row: jax.Array,
                   batch: dict, slots: int, *, kv_bits: int = 16,
                   prefix_k: jax.Array, prefix_v: jax.Array,
                   prefix_len: jax.Array,
                   prefix_k_amax: Optional[jax.Array] = None,
                   prefix_v_amax: Optional[jax.Array] = None,
                   return_raw_kv: bool = False,
                   kv_sched: Optional[jax.Array] = None):
    """Shared-prefix prefill → (last-token logits, dense decode caches).

    Runs :func:`forward_extend` over the suffix only, then builds the same
    dense ``[B, slots]`` row caches a cold :func:`prefill` of the full
    prompt would: prefix K/V land at logical positions ``0..prefix_len−1``
    (re-cast / re-quantized from the full-precision masters), suffix K/V at
    ``prefix_len..prompt_len−1``, everything else stays at the ``token_idx
    = −1`` empty sentinel. For int KV the per-row dequant scale is
    calibrated as ``max(prefix amax, suffix amax)`` — *exactly* the scale a
    cold prefill over all real tokens computes (``prefix_*_amax [L, B,
    Hkv]`` are the raw max-|K|/|V| over real prefix tokens, snapshotted at
    registration) — so the quantized ints, and every decode step after
    them, match the cold path. The caller scatters the resulting rows into
    pool blocks, skipping the shared ones (copy-on-write: shared blocks are
    never written, divergent content lands in private blocks).

    ``return_raw_kv`` additionally returns the pre-quantization suffix K/V
    (``(k, v)`` each ``[L, B, Sb, Hkv, hd]``, padded column coordinates) —
    what chunked prefill accumulates host-side so the *next* chunk can
    replay this one as its prefix masters at int KV precisions.
    """
    hidden, kv_col = forward_extend(params, cfg, bits_row, batch,
                                    prefix_k, prefix_v, prefix_len,
                                    kv_sched=kv_sched)
    b, s, _ = hidden.shape
    caches = init_caches(cfg, b, slots, kv_bits=kv_bits)
    k_all, v_all = kv_col                        # [L, B, Sb, Hkv, hd]
    eff = caches["kv"].token_idx.shape[-1]
    pp = prefix_k.shape[2]
    plen = jnp.asarray(prefix_len, jnp.int32)
    slen = jnp.asarray(batch["prompt_len"], jnp.int32)
    ppos = jnp.arange(pp, dtype=jnp.int32)
    real_p = ppos[None] < plen[:, None]                   # [B, Pp]
    slot_p = jnp.where(real_p, ppos[None], eff)           # OOB → drop
    tokw_p = jnp.where(real_p, ppos[None], -1)
    local = jnp.arange(s, dtype=jnp.int32)[None] - (s - slen)[:, None]
    pos_s = local + plen[:, None]                         # [B, Sb] absolute
    real_s = local >= 0
    slot_s = jnp.where(real_s, pos_s, eff)
    tokw_s = jnp.where(real_s, pos_s, -1)
    ridx = jnp.arange(b)[:, None]

    def fill(kvc, k_l, v_l, kp_l, vp_l, ka_l, va_l):
        if kvc.bits in (4, 8):
            from repro.models.attention import _quantize_kv
            qmax = 127.0 if kvc.bits == 8 else 7.0
            ka = jnp.where(real_s[:, :, None, None],
                           jnp.abs(k_l.astype(jnp.float32)), 0.0)
            va = jnp.where(real_s[:, :, None, None],
                           jnp.abs(v_l.astype(jnp.float32)), 0.0)
            ks = jnp.maximum(jnp.max(ka, axis=(1, 3)), ka_l) / qmax + 1e-9
            vs = jnp.maximum(jnp.max(va, axis=(1, 3)), va_l) / qmax + 1e-9
            kq_s, vq_s = _quantize_kv(k_l, ks, kvc.bits), \
                _quantize_kv(v_l, vs, kvc.bits)
            kq_p, vq_p = _quantize_kv(kp_l, ks, kvc.bits), \
                _quantize_kv(vp_l, vs, kvc.bits)
        else:
            ks, vs = kvc.k_scale, kvc.v_scale
            kq_s, vq_s = k_l.astype(kvc.k.dtype), v_l.astype(kvc.v.dtype)
            kq_p, vq_p = kp_l.astype(kvc.k.dtype), vp_l.astype(kvc.v.dtype)
        k = kvc.k.at[ridx, slot_p].set(kq_p, mode="drop")
        v = kvc.v.at[ridx, slot_p].set(vq_p, mode="drop")
        ti = kvc.token_idx.at[ridx, slot_p].set(tokw_p, mode="drop")
        return KVCache(
            k=k.at[ridx, slot_s].set(kq_s, mode="drop"),
            v=v.at[ridx, slot_s].set(vq_s, mode="drop"),
            k_scale=ks, v_scale=vs,
            token_idx=ti.at[ridx, slot_s].set(tokw_s, mode="drop"),
            bits=kvc.bits,
        )

    if prefix_k_amax is None:
        prefix_k_amax = jnp.zeros((cfg.n_layers, b, cfg.n_kv), jnp.float32)
    if prefix_v_amax is None:
        prefix_v_amax = jnp.zeros((cfg.n_layers, b, cfg.n_kv), jnp.float32)
    caches["kv"] = jax.vmap(fill)(caches["kv"], k_all, v_all,
                                  prefix_k, prefix_v,
                                  prefix_k_amax, prefix_v_amax)
    logits = _logits(cfg, params, bits_row, hidden[:, -1:])[:, 0]
    if return_raw_kv:
        return logits, caches, kv_col
    return logits, caches
