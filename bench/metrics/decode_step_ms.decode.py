"""Device time of the decode-segment executable in the traced part of the
window, per decode step (each run is quantum steps)."""

UNIT, LAYER, MOVES = "ms", "decode segment", "output_tok_s"


def read(rec):
    seg = rec["trace"].get("modules", {}).get("jit_segment_fn")
    if not seg or not seg["runs"]:
        return None
    return seg["s"] * 1e3 / (seg["runs"] * rec["quantum"])
