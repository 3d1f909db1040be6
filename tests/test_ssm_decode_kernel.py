"""The Mamba-2 decode state-update kernel (``ssm_decode_pallas``, interpret
mode) against its XLA form ``repro.models.ssm.ssm_recur``: one layer of a
stacked state updated in place — that layer's new state and the output
match, every other layer's bytes are untouched, and the state operand is
aliased to the kernel's output — at tiny and uneven shapes (one group or
several, heads per block below the group's, a state width that is no
multiple of 128)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ssm_decode import heads_per_block, ssm_decode_pallas
from repro.models.ssm import ssm_recur

# (layers, rows, heads, head dim P, state N, groups)
SHAPES = [(3, 5, 8, 16, 16, 1), (2, 3, 6, 8, 24, 2), (1, 1, 4, 8, 128, 4),
          (4, 2, 12, 16, 40, 3)]


def _operands(shape, seed=0):
    n_l, r, h, p, n, g = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(ks[0], (n_l, r, h, p, n)),
            jax.random.normal(ks[1], (r, h, p)),
            jax.nn.softplus(jax.random.normal(ks[2], (r, h))),
            -jnp.exp(jax.random.normal(ks[3], (h,))),
            jax.random.normal(ks[4], (r, g, n)),
            jax.random.normal(ks[5], (r, g, n)),
            jax.random.normal(ks[6], (h,)))


@pytest.mark.parametrize("shape", SHAPES)
def test_kernel_matches_xla_form_in_place(shape):
    h, x, dt, a, b, c, d = _operands(shape)
    before = np.asarray(h)
    layer = shape[0] - 1
    y_want, h_want = ssm_recur(h[layer], x, dt, a, b, c, d)
    y, h_new = ssm_decode_pallas(jnp.array(h), jnp.int32(layer), x, dt, a,
                                 b, c, d, interpret=True)
    h_new = np.asarray(h_new)
    # f32 on both sides; the kernel sums h.C along lanes, the XLA form
    # contracts with an einsum: they differ by summation order only
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h_new[layer], np.asarray(h_want),
                               rtol=1e-6, atol=1e-6)
    others = [i for i in range(shape[0]) if i != layer]
    assert (h_new[others] == before[others]).all()


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, inner jaxprs (the jit) included."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _eqns(inner)


def test_state_operand_is_aliased_to_the_output():
    ops = _operands(SHAPES[0])
    jaxpr = jax.make_jaxpr(lambda h, *a: ssm_decode_pallas(
        h, jnp.int32(1), *a, interpret=True))(*ops)
    calls = [e for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    # operand 1 (after the scalar-prefetched layer) is output 1
    assert tuple(calls[0].params["input_output_aliases"]) == ((1, 1),)


@pytest.mark.parametrize("heads,groups,p,n,want", [
    (64, 1, 64, 128, 64),      # the cell: one 2 MiB block per row
    (128, 1, 64, 128, 64),     # a block stays within 2 MiB
    (8, 2, 16, 16, 4),         # never across a group
])
def test_heads_per_block(heads, groups, p, n, want):
    assert heads_per_block(heads, groups, p, n) == want
