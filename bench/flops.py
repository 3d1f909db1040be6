"""Operations and bytes the serving work needs, from shapes alone, for
what every architecture shares: one paged-attention call and the roofline.

``sz`` is the ``sizes`` of a configuration's architecture module
(``arch/<name>.py``), which counts the work of its whole model itself
(``decode_token_flops``, ``prefill_flops``); a metric reader takes that
module from the run's record. Counts are of the algorithm's need, not of
what the program happens to execute: attention over the ``ctx`` keys a row
really holds, matmuls over real (unpadded) tokens. A multiply-add is two
operations.
"""
from __future__ import annotations


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
