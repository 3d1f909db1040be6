"""Compile rehearsals for a v5e chip: the paged-attention kernels of the
serving path at granite-3-2b's attention geometry (8 KV heads, 4 query heads
per KV head, head dim 64, 16-token blocks), compiled for a described chip
that is not attached, at two table geometries: 16 logical blocks of 256,
and the batch-decode cell's 49 of 392 (49 is no multiple of the pages a
grid step takes); and at qwen2-vl-2b-text's cell (128 rows, 2 KV heads of
128, group 6, 6272 blocks, 49 a row). Also the Mamba-2 decode state update
at granite-4.0-h-micro's cell (its rows, 64 heads, P 64, N 128, f32 state,
36 layers). The TPU compiler refuses here what interpret mode never
checks (block shapes off the (8, 128) tiling, unsupported vector relayouts),
so these guard every change to the kernels at no chip time. They also pin
the kernels' op names, which the benchmark's trace reduction matches.

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
from repro.kernels.ssm_decode import ssm_decode_pallas

B, HKV, HG, D, BS, W = 8, 8, 4, 64, 16, 5
# (N_BLOCKS, N_LBLK)
GEOMETRIES = {"lblk16": (256, 16), "cell": (392, 49)}
# qwen2-vl-2b-text.batch-decode: rows, KV heads, query heads per KV head,
# D, and (N_BLOCKS, N_LBLK)
QWEN = (128, 2, 6, 128, (6272, 49))
# granite-4.0-h-micro.batch-decode: Mamba layers, rows, heads, P, N, groups
SSM_CELL = (36, 16, 64, 64, 128, 1)


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


def _pool_args(sharding, bits, q_shape, scale_shape, geometry, hkv=HKV,
               d=D, b=B):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    n_blocks, n_lblk = GEOMETRIES.get(geometry, geometry)
    dk = d // 2 if bits == 4 else d
    pool_dt = jnp.bfloat16 if bits == 16 else jnp.int8
    return (s(q_shape, jnp.float32), s((n_blocks, BS, hkv, dk), pool_dt),
            s((n_blocks, BS, hkv, dk), pool_dt), s(scale_shape, jnp.float32),
            s(scale_shape, jnp.float32), s((n_blocks, BS), jnp.int32),
            s((b, n_lblk), jnp.int32), s((b,), jnp.int32))


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


def test_paged_attention_compiles_for_v5e_at_qwen_geometry(one_chip):
    b, hkv, hg, d, blocks = QWEN
    args = _pool_args(one_chip, 16, (b, hkv, hg, d), (b, hkv), blocks,
                      hkv=hkv, d=d, b=b)
    _one_kernel(_compiled_hlo(
        lambda *a: paged_attention_pallas(*a, bits=16), args))


def test_ssm_decode_compiles_for_v5e_in_place(one_chip):
    """One Mosaic call named ``ssm_decode_pallas``, and with the state
    donated no copy of it: the update is in place."""
    n_l, r, h, p, n, g = SSM_CELL

    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    args = (s((n_l, r, h, p, n)), s((), jnp.int32), s((r, h, p)),
            s((r, h)), s((h,)), s((r, g, n)), s((r, g, n)), s((h,)))
    hlo = jax.jit(ssm_decode_pallas, donate_argnums=(0,)).lower(
        *args).compile().as_text()
    ops = _kernel_ops(hlo)
    assert len(ops) == 1 and ops[0].startswith("ssm_decode_pallas"), ops
    state = f"f32[{n_l},{r},{h},{p},{n}]"
    assert not [ln for ln in hlo.splitlines()
                if " copy(" in ln and ln.split("=")[1].strip().startswith(
                    state)], "the stacked state is copied"
