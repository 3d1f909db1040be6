"""Share of the traced part of the window in which no operation ran on the
device (1 - busy union / window)."""

UNIT, LAYER, MOVES = "%", "device", "output_tok_s"


def read(rec):
    t = rec["trace"]
    if not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
