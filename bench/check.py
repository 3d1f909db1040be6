"""Whether what the timed window served is correct.

After the window has closed and the program's state is freed, a sample of
the requests the window finished — drawn from the seed, always holding the
one with most served tokens — is read by the plain reference (the
architecture module's ``make_forward``) once over its prompt and served
tokens. The number compared is the widest gap by which a served token's
reference logit lies below the reference's best at its position, over
every sampled token. The cell's file states its limit (``check.max_gap``)
and the readings it was set from.

The control puts the reference computed one precision lower (activations
on the 8-bit grid instead of the 16-bit one) in the program's place: at
each position of the same sequences, the token it puts first replaces the
served token, and the verdict reads it the same way. It must come out not
correct; benchmark runs do not run it.
"""
from __future__ import annotations

import numpy as np

from . import reference as R


def sample(finished: list, seed: int, min_tokens: int, max_requests: int):
    """The longest finished request, then others in a seeded order until
    ``min_tokens`` served tokens or ``max_requests`` requests."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.tokens), -r.submit))
    rest = [r for r in finished if r is not longest]
    order = np.random.default_rng([int(seed), 3]).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def gaps(arch, params, sz: dict, conf: dict, reqs: list, pad_to: int,
         control: bool = False) -> dict:
    """Widest gap over ``reqs`` of the tokens judged: the served ones, or
    with ``control`` the lower-precision reference's first choices in their
    place (``served_gap`` then keeps the served tokens' reading)."""
    if not reqs:
        nothing = {"gap": float("inf"), "tokens": 0, "requests": 0}
        return nothing | ({"served_gap": float("inf")} if control else {})
    a_bits, w_bits = conf["a_bits"], conf["w_bits"]
    fwd = arch.make_forward(sz, a_bits=a_bits, w_bits=w_bits)
    low = arch.make_forward(sz, a_bits=conf["control_a_bits"],
                            w_bits=w_bits) if control else None
    widest, widest_served, n_tok = 0.0, 0.0, 0
    for r in reqs:
        served = np.asarray(r.tokens, np.int64)
        seq, at = R.teacher_forced(r.prompt, served, pad_to)
        ref = np.asarray(fwd(params, seq))[at]
        widest_served = max(widest_served,
                            float(R.served_gaps(ref, served).max()))
        judged = served if low is None else \
            np.asarray(low(params, seq))[at].argmax(axis=-1)
        widest = max(widest, float(R.served_gaps(ref, judged).max()))
        n_tok += len(served)
    out = {"gap": widest, "tokens": n_tok, "requests": len(reqs)}
    if control:
        out["served_gap"] = widest_served
    return out


def verdict(gap: float, n_requests: int, malformed: int,
            check: dict) -> tuple[bool, dict]:
    """``(correct, compared)``: every number compared beside its limit."""
    compared = {"widest_gap": {"value": gap, "limit": check["max_gap"]},
                "malformed_outputs": {"value": malformed, "limit": 0}}
    return (n_requests > 0 and gap <= check["max_gap"] and malformed == 0,
            compared)
