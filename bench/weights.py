"""What every architecture's seeded weights share.

Each ``arch/<name>.py`` makes its weights with ``make_weights``: one jitted
call on the device makes every leaf in the dtype it is served in (matrices
in the program's compute dtype, norm gains and biases in f32), in the
layout the program's transformer reads, from keys of ``key_of``. The values
come from the seed alone, so the reference reads the same weights without
taking anything the program has made; ``check_layout`` holds the made tree
to the program's own.
"""
from __future__ import annotations

import jax
import numpy as np


def key_of(seed: int, stream: int) -> jax.Array:
    """A JAX key for ``stream`` of ``seed`` (any non-negative int, also past
    32 bits)."""
    state = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.PRNGKey(int(state[0]) & 0x7FFFFFFF)


def check_layout(params: dict, expected) -> None:
    """The made tree has the structure, shapes and dtypes of ``expected``
    (the program's own parameter tree, as shapes only)."""
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), expected)
    if got != want:
        raise ValueError(f"benchmark weights do not match the program's "
                         f"layout:\n got  {got}\n want {want}")
