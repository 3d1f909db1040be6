"""Compile rehearsals for a v5e chip: the paged-attention kernels of the
serving path at granite-3-2b's attention geometry (8 KV heads, 4 query heads
per KV head, head dim 64, 16-token blocks), compiled for a described chip
that is not attached, at two table geometries: 16 logical blocks of 256,
and the batch-decode cell's 49 of 392 (49 is no multiple of the pages a
grid step takes). The TPU compiler refuses here what interpret mode never
checks (block shapes off the (8, 128) tiling, unsupported vector relayouts),
so these guard every change to the kernels at no chip time. They also pin
the kernel's op name, which the benchmark's trace reduction matches.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import (paged_attention_pallas,
                                           paged_attention_pallas_multi)

B, HKV, HG, D, BS, W = 8, 8, 4, 64, 16, 5
# (N_BLOCKS, N_LBLK)
GEOMETRIES = {"lblk16": (256, 16), "cell": (392, 49)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _pool_args(sharding, bits, q_shape, scale_shape, geometry):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    n_blocks, n_lblk = GEOMETRIES[geometry]
    dk = D // 2 if bits == 4 else D
    pool_dt = jnp.bfloat16 if bits == 16 else jnp.int8
    return (s(q_shape, jnp.float32), s((n_blocks, BS, HKV, dk), pool_dt),
            s((n_blocks, BS, HKV, dk), pool_dt), s(scale_shape, jnp.float32),
            s(scale_shape, jnp.float32), s((n_blocks, BS), jnp.int32),
            s((B, n_lblk), jnp.int32), s((B,), jnp.int32))


def _compiled_hlo(fn, args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_ops(hlo: str) -> list[str]:
    """Names of the compiled Mosaic kernel calls (``%<name> = ...``)."""
    return re.findall(r"%(\S+) = .*custom_call_target=\"tpu_custom_call\"",
                      hlo)


def _one_kernel(hlo: str) -> None:
    """Exactly one Mosaic call, under the name the trace reduction sums."""
    ops = _kernel_ops(hlo)
    assert len(ops) == 1 and ops[0].startswith("paged_attention_pallas"), ops


@pytest.mark.parametrize("geometry", list(GEOMETRIES))
@pytest.mark.parametrize("bits", [16, 8, 4])
def test_paged_attention_compiles_for_v5e(one_chip, bits, geometry):
    args = _pool_args(one_chip, bits, (B, HKV, HG, D), (B, HKV), geometry)
    _one_kernel(_compiled_hlo(
        lambda *a: paged_attention_pallas(*a, bits=bits), args))


@pytest.mark.parametrize("geometry", list(GEOMETRIES))
@pytest.mark.parametrize("bits", [16, 8])
def test_paged_attention_multi_compiles_for_v5e(one_chip, bits, geometry):
    args = _pool_args(one_chip, bits, (B, W, HKV, HG, D), (B, W, HKV),
                      geometry)
    _one_kernel(_compiled_hlo(
        lambda *a: paged_attention_pallas_multi(*a, bits=bits), args))
