"""Operations and bytes the serving work needs, from shapes alone.

``sz`` is :func:`bench.weights.sizes` of a configuration. Counts are of the
algorithm's need, not of what the program happens to execute: attention
over the ``ctx`` keys a row really holds, matmuls over real (unpadded)
tokens. A multiply-add is two operations.
"""
from __future__ import annotations


def matmul_params(sz: dict) -> int:
    """Weights one token multiplies through: the layers' projections and
    the (tied) output head. The embedding gather does no arithmetic."""
    d, H, K, hd, ff = sz["d"], sz["H"], sz["K"], sz["hd"], sz["ff"]
    layer = d * (H + 2 * K) * hd + H * hd * d + d * 2 * ff + ff * d
    return sz["L"] * layer + d * sz["V"]


def attn_flops(sz: dict, ctx: int) -> int:
    """One query over ``ctx`` keys, all layers: ``q.k`` and ``p.v``."""
    return 4 * ctx * sz["H"] * sz["hd"] * sz["L"]


def decode_token_flops(sz: dict, ctx: int) -> int:
    """One decode step of one row that attends over ``ctx`` keys."""
    return 2 * matmul_params(sz) + attn_flops(sz, ctx)


def prefill_flops(sz: dict, n: int) -> int:
    """A causal prompt of ``n`` tokens: query ``i`` attends over ``i + 1``
    keys."""
    return 2 * matmul_params(sz) * n + 4 * sz["H"] * sz["hd"] * sz["L"] \
        * (n * (n + 1) // 2)


def kv_bytes_per_token(sz: dict, kv_bits: int) -> int:
    """Key and value of one token in every layer's pool."""
    return 2 * sz["L"] * sz["K"] * sz["hd"] * kv_bits // 8


def paged_kernel_call(sz: dict, ctx: int, kv_bits: int = 16) -> tuple[int, int]:
    """(operations, bytes) of one row's paged-attention call in one layer:
    the query against ``ctx`` cached keys. Bytes: keys and values, their
    token indices (int32), the query (bf16) and the output (f32)."""
    H, K, hd = sz["H"], sz["K"], sz["hd"]
    ops = 4 * ctx * H * hd
    byts = ctx * (2 * K * hd * kv_bits // 8 + 4) + H * hd * (2 + 4)
    return ops, byts


def least_time_s(ops: float, byts: float, peak: dict) -> float:
    """The roofline's lower bound on a call's time."""
    return max(ops / peak["bf16_flops"], byts / peak["hbm_bytes_per_s"])
