"""Paged-attention kernel's share of its roofline: the least time its calls
need (per call, the larger of operations / peak and bytes / HBM bandwidth,
from the context each live row held at that step) over the kernel's device
time, both over the traced part of the window, in each of the layers whose
KV lives in the paged pool (``sz["L_attn"]``). Dead rows and unused blocks
the kernel still visits count as time, not as need."""
from bench import flops

UNIT, LAYER, MOVES = "%", "kernels", "output_tok_s"
KERNEL = "paged_attention_pallas"


def read(rec):
    k = rec["trace"].get("kernels", {}).get(KERNEL)
    if not k or not k["s"]:
        return None
    sz, q, peak = rec["sz"], rec["quantum"], rec["peak"]
    k0, k1 = rec["traced_steps"]
    need = 0.0
    for step, rows in rec["segments"]:
        if not k0 <= step < k1:
            continue
        for i in range(q):
            ops = byts = 0
            for c0, n in rows:
                if n > i:
                    o, b = flops.paged_kernel_call(sz, c0 + i, rec["kv_bits"])
                    ops, byts = ops + o, byts + b
            if ops:
                need += sz["L_attn"] * flops.least_time_s(ops, byts, peak)
    return 100.0 * need / k["s"] if need else None
