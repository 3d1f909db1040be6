"""Mean over the window's decode steps of the rows still generating,
divided by max_batch (the scheduler's remaining counts per segment)."""

UNIT, LAYER, MOVES = "%", "scheduler", "output_tok_s"


def read(rec):
    q, mb = rec["quantum"], rec["max_batch"]
    steps = [sum(1 for _, n in rows if n > i)
             for _, rows in rec["segments"] for i in range(q)]
    return 100.0 * sum(steps) / (len(steps) * mb) if steps else None
