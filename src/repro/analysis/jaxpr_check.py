"""Invariant checks over traced jaxprs and compiled executables.

These formalize what the tests previously hand-rolled: walk every
equation (recursing into nested jaxprs carried in eqn params, e.g.
``scan``/``cond``/``pjit`` bodies), and assert properties of the
intermediate avals — byte ceilings, forbidden shapes, primitive counts —
plus donation verification via the lowered executable text.
"""

from __future__ import annotations

from collections import Counter
from dataclasses import dataclass
from typing import Callable, Iterable

import numpy as np

from jax.extend.core import Jaxpr, JaxprEqn

_TRANSFER_PRIMITIVES = {"device_put", "convert_element_type_to_host", "copy"}


def _nested_jaxprs(eqn: JaxprEqn) -> Iterable[Jaxpr]:
    for val in eqn.params.values():
        objs = val if isinstance(val, (list, tuple)) else [val]
        for obj in objs:
            if hasattr(obj, "jaxpr"):  # ClosedJaxpr
                yield obj.jaxpr
            elif isinstance(obj, Jaxpr):
                yield obj


def iter_eqns(jaxpr) -> Iterable[JaxprEqn]:
    """Yield every equation in ``jaxpr``, recursing into nested jaxprs."""
    if hasattr(jaxpr, "jaxpr"):  # ClosedJaxpr
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _nested_jaxprs(eqn):
            yield from iter_eqns(sub)


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        n = int(np.prod(shape)) if shape else 1
        return n * np.dtype(dtype).itemsize
    except Exception:
        return 0


@dataclass(frozen=True)
class AvalViolation:
    primitive: str
    shape: tuple
    dtype: str
    nbytes: int

    def render(self) -> str:
        return (
            f"{self.primitive}: {self.dtype}{list(self.shape)} = "
            f"{self.nbytes:,} bytes"
        )


def max_aval_bytes(jaxpr) -> int:
    """Largest intermediate aval (in bytes) anywhere in the jaxpr."""
    best = 0
    for eqn in iter_eqns(jaxpr):
        for var in eqn.outvars:
            best = max(best, _aval_bytes(var.aval))
    return best


def check_aval_budget(jaxpr, budget_bytes: int) -> list[AvalViolation]:
    """Every intermediate aval whose size exceeds ``budget_bytes``."""
    out: list[AvalViolation] = []
    for eqn in iter_eqns(jaxpr):
        for var in eqn.outvars:
            nbytes = _aval_bytes(var.aval)
            if nbytes > budget_bytes:
                aval = var.aval
                out.append(
                    AvalViolation(
                        str(eqn.primitive),
                        tuple(getattr(aval, "shape", ())),
                        str(getattr(aval, "dtype", "?")),
                        nbytes,
                    )
                )
    return out


def forbid_aval_shape(jaxpr, pred: Callable[[tuple], bool]) -> list[AvalViolation]:
    """Every intermediate aval whose shape satisfies ``pred``."""
    out: list[AvalViolation] = []
    for eqn in iter_eqns(jaxpr):
        for var in eqn.outvars:
            shape = tuple(getattr(var.aval, "shape", ()))
            if shape and pred(shape):
                out.append(
                    AvalViolation(
                        str(eqn.primitive),
                        shape,
                        str(getattr(var.aval, "dtype", "?")),
                        _aval_bytes(var.aval),
                    )
                )
    return out


def has_adjacent_dims(jaxpr, dims: tuple[int, int]) -> bool:
    """True if any intermediate aval has ``dims`` as adjacent dimensions.

    This is the gather-view signature: the materialized paged view is
    ``[B, n_lblk*bs]``-shaped (batch adjacent to padded slot count), which
    the in-place pallas path must never produce.
    """
    a, b = dims

    def pred(shape: tuple) -> bool:
        return any(
            shape[i] == a and shape[i + 1] == b for i in range(len(shape) - 1)
        )

    return bool(forbid_aval_shape(jaxpr, pred))


def count_primitives(jaxpr) -> Counter:
    """Histogram of primitive names over the whole (recursive) jaxpr."""
    return Counter(str(eqn.primitive) for eqn in iter_eqns(jaxpr))


def count_transfers(jaxpr) -> int:
    """Number of explicit host/device transfer primitives in the jaxpr."""
    counts = count_primitives(jaxpr)
    return sum(counts[p] for p in _TRANSFER_PRIMITIVES)


def verify_donation(jitted, *args, **kwargs) -> bool:
    """True if the lowered executable aliases at least one input buffer to
    an output (i.e. donation actually took effect, not just requested).

    Works by lowering with the given abstract/concrete args and searching
    the StableHLO text for the aliasing attribute; robust across jax
    versions that do not expose ``input_output_aliases`` on Compiled.
    """
    lowered = jitted.lower(*args, **kwargs)
    text = lowered.as_text()
    return "tf.aliasing_output" in text or "jax.buffer_donor" in text
