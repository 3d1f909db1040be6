"""Pipeline parallelism across pods — the streaming-architecture analogue.

The paper's FPGA engine is a *spatial pipeline*: one hardware block per layer,
activations streaming block-to-block through on-chip FIFOs. At fleet scale the
same shape is pipeline parallelism: each pod owns a contiguous stage of layers
and microbatches stream stage-to-stage over the (slow) inter-pod links — the
exact reason the multi-pod mesh has a dedicated ``pod`` axis (DESIGN §5).

GPipe-style schedule inside ``shard_map`` over the stage axis:

    t = 0 .. (M + S − 2):   stage s processes microbatch (t − s) when valid;
    activations hop s → s+1 via ``lax.ppermute`` each tick.

The loop is a ``lax.fori_loop`` (compile-time compact); bubbles are the usual
(S−1)/(M+S−1) fraction. Forward-only here (the serving/streaming analogue);
training composes it with ``jax.grad`` through the loop or uses DP across
pods instead (the dry-run default).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_forward", "stage_split"]


def stage_split(params_stacked, n_stages: int):
    """Reshape layer-stacked params [L, ...] → [S, L/S, ...] (stage-major)."""
    def one(a):
        l = a.shape[0]
        assert l % n_stages == 0, f"{l} layers don't split into {n_stages} stages"
        return a.reshape(n_stages, l // n_stages, *a.shape[1:])
    return jax.tree.map(one, params_stacked)


def pipeline_forward(stage_fn: Callable, params_staged, x: jax.Array, *,
                     mesh, axis_name: str = "pod",
                     n_microbatches: int) -> jax.Array:
    """Run ``x [B, ...]`` through S pipeline stages, microbatched.

    ``stage_fn(stage_params, xm) -> xm`` applies one stage's layers to one
    microbatch. ``params_staged`` has leading dim S (from :func:`stage_split`),
    sharded so stage s lives on pod s. Returns y with stage-S output for every
    microbatch, reassembled to ``[B, ...]``.
    """
    n_stages = mesh.shape[axis_name]
    b = x.shape[0]
    assert b % n_microbatches == 0
    mb = b // n_microbatches
    xm = x.reshape(n_microbatches, mb, *x.shape[1:])

    in_specs = (P(axis_name), P())      # params by stage; microbatches everywhere
    out_specs = P()

    def body(params_local, xm_all):
        # params_local: [1, L/S, ...] — this pod's stage
        sp = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis_name)
        n_ticks = n_microbatches + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (while valid); others use buf
            feed = jax.lax.dynamic_index_in_dim(
                xm_all, jnp.minimum(t, n_microbatches - 1), keepdims=False)
            x_in = jnp.where(stage == 0, feed, buf)
            y = stage_fn(sp, x_in)
            mb_idx = t - (n_stages - 1)       # microbatch exiting last stage
            is_out = (mb_idx >= 0) & (stage == n_stages - 1)
            mb_c = jnp.clip(mb_idx, 0, n_microbatches - 1)
            row = jnp.where(is_out, y,
                            jax.lax.dynamic_index_in_dim(outs, mb_c,
                                                         keepdims=False))
            outs = jax.lax.dynamic_update_index_in_dim(outs, row, mb_c, axis=0)
            buf = jax.lax.ppermute(y, axis_name, perm)
            return buf, outs

        # carries become device-varying inside the loop → mark them upfront
        buf0 = jax.lax.pcast(jnp.zeros_like(xm_all[0]), (axis_name,),
                             to="varying")
        outs0 = jax.lax.pcast(jnp.zeros_like(xm_all), (axis_name,),
                              to="varying")
        _, outs = jax.lax.fori_loop(0, n_ticks, tick, (buf0, outs0))
        # only the last stage holds real outputs; broadcast via max-reduce
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis_name)
        return outs

    y = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)(params_staged, xm)
    return y.reshape(b, *x.shape[1:])
