"""Mamba-2 decode state-update kernel's share of its roofline: the least
time its calls need (per call, the larger of operations / peak and bytes /
HBM bandwidth over the rows live at that step, from the architecture
module's ``ssm_decode_call``), in each of the ``L - L_attn`` Mamba layers,
over the kernel's device time, both over the traced part of the window.
Dead rows the kernel still updates count as time, not as need."""
from bench import flops

UNIT, LAYER, MOVES = "%", "kernels", "output_tok_s"
KERNEL = "ssm_decode_pallas"


def read(rec):
    k = rec["trace"].get("kernels", {}).get(KERNEL)
    call = getattr(rec["arch"], "ssm_decode_call", None)
    if not k or not k["s"] or call is None:
        return None
    sz, q, peak = rec["sz"], rec["quantum"], rec["peak"]
    ops, byts = call(sz)
    k0, k1 = rec["traced_steps"]
    need = 0.0
    for step, rows in rec["segments"]:
        if not k0 <= step < k1:
            continue
        for i in range(q):
            live = sum(1 for _, n in rows if n > i)
            if live:
                need += (sz["L"] - sz["L_attn"]) * flops.least_time_s(
                    live * ops, live * byts, peak)
    return 100.0 * need / k["s"] if need else None
