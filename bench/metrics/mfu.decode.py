"""The whole step's share of the chip's bf16 peak over the traced part of
the window: the operations the work of the traced steps needs (2 x matmul
weights + attention at each row's real context per decode token, and each
admitted prompt's causal prefill), over the traced span's length times the
peak. The device is drained at both ends of the span, so the traced steps'
work runs inside it, and the profiler's start and stop lie outside. The
counts are the run's architecture module's (``rec["arch"]``)."""

UNIT, LAYER, MOVES = "%", "decode step", "output_tok_s"


def read(rec):
    span = rec["trace"].get("window_s")
    if not span:
        return None
    sz, arch = rec["sz"], rec["arch"]
    k0, k1 = rec["traced_steps"]
    ops = sum(arch.decode_token_flops(sz, c0 + i)
              for step, rows in rec["segments"] if k0 <= step < k1
              for c0, n in rows for i in range(n))
    ops += sum(arch.prefill_flops(sz, p) for step, p in rec["admitted"]
               if k0 <= step < k1)
    if not ops:
        return None
    return 100.0 * ops / (span * rec["peak"]["bf16_flops"])
