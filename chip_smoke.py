"""Smoke run of the serving path on one TPU chip.

One process, no options, no files from outside the repository:

1. the paged-attention kernels, compiled for the chip at granite-3-2b's
   attention geometry (8 KV heads, 4 query heads per KV head, head dim 64,
   16-token blocks), against their jnp oracles: the single-query kernel at
   kv16/kv8/kv4 and the speculative-window kernel (W = 5) at kv16/kv8;
2. the full-width granite-3-2b server, built as ``python -m
   repro.launch.serve --full --continuous`` builds it (paged KV pool, kv16,
   ``auto`` decode backend), draining 8 seeded requests of 32-192 prompt
   tokens and 32 new tokens each.

Weights are random from a fixed seed. The timings it prints are those of a
smoke run, not a benchmark. The last line of standard output is one JSON
object naming the device. Anything but a TPU is an error: the script exits
non-zero and prints no result.

    python chip_smoke.py
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ref  # noqa: E402
from repro.kernels.paged_attention import (  # noqa: E402
    paged_attention_pallas, paged_attention_pallas_multi)
from repro.launch import serve  # noqa: E402
from repro.models.attention import (  # noqa: E402
    PagedKVCache, decode_attention_window, paged_view)
from repro.runtime import enable_compile_cache  # noqa: E402
from repro.serving.engine import Request, RequestStatus  # noqa: E402
from repro.serving.scheduler import ContinuousScheduler  # noqa: E402

HKV, HG, D, BS, N_LBLK, N_BLOCKS, W = 8, 4, 64, 16, 16, 64, 5
# kernel vs oracle: |kernel - oracle| <= ATOL * max|oracle| + RTOL * |oracle|
ATOL, RTOL = 1e-2, 1e-2
N_REQ, MAX_NEW = 8, 32


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require(cond, what) -> None:
    """A failed check ends the run (unlike ``assert``, also under ``-O``)."""
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def bf16_exact(x) -> np.ndarray:
    """Round to bf16 and back: products of such values are exact in f32,
    so kernel/oracle differences come from accumulation and softmax only."""
    return np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32))


def pool_case(rng, lengths, bits, *, w=1):
    """Fragmented paged state at granite geometry: out-of-order physical
    blocks, lengths on and off block boundaries; a length of 0 is a dead
    row whose table holds only the unmapped sentinel."""
    b = len(lengths)
    q = bf16_exact(rng.normal(size=(b, w, HKV, HG, D)))
    dk = D // 2 if bits == 4 else D
    shape = (N_BLOCKS, BS, HKV, dk)
    if bits == 16:
        kp, vp = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                  for _ in range(2))
        ks = vs = np.ones((b, w, HKV), np.float32)
    else:
        lo, hi = (-128, 128) if bits == 4 else (-127, 128)   # kv4: any byte
        kp, vp = (jnp.asarray(rng.integers(lo, hi, shape), jnp.int8)
                  for _ in range(2))
        ks, vs = (bf16_exact(rng.uniform(0.01, 0.1, (b, w, HKV)))
                  for _ in range(2))
    perm = rng.permutation(N_BLOCKS)
    tidx = np.full((N_BLOCKS, BS), -1, np.int32)
    bt = np.full((b, N_LBLK), N_BLOCKS, np.int32)
    pos = np.zeros((b,), np.int32)
    nxt = 0
    for r, ln in enumerate(lengths):
        pos[r] = max(ln - w, 0)             # the window's first query position
        for lb in range(-(-ln // BS)):
            p = int(perm[nxt])
            nxt += 1
            bt[r, lb] = p
            nv = min(ln - lb * BS, BS)
            tidx[p, :nv] = lb * BS + np.arange(nv)
    return (jnp.asarray(q), kp, vp, jnp.asarray(ks), jnp.asarray(vs),
            jnp.asarray(tidx), jnp.asarray(bt), jnp.asarray(pos))


def check_close(name: str, out, want) -> None:
    out, want = np.asarray(out), np.asarray(want)
    require(out.shape == want.shape and np.isfinite(out).all(), name)
    err = float(np.abs(out - want).max())
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(out, want, rtol=RTOL, atol=ATOL * scale,
                               err_msg=name)
    log(f"{name}: max|err| {err:.3e} (bound {ATOL:g}*{scale:.3g} + "
        f"{RTOL:g}*|oracle|)")


def kernel_hlo(fn, *args) -> str:
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    require("tpu_custom_call" in hlo, "kernel did not compile to a TPU kernel")
    return hlo


def check_kernels() -> None:
    rng = np.random.default_rng(0)
    lengths = (1, 15, 16, 17, 100, 200, 256, 0)
    for bits in (16, 8, 4):
        q, kp, vp, ks, vs, tidx, bt, pos = pool_case(rng, lengths, bits)
        args = (q[:, 0], kp, vp, ks[:, 0], vs[:, 0], tidx, bt, pos)

        def kern(*a, bits=bits):
            return paged_attention_pallas(*a, bits=bits)

        kernel_hlo(kern, *args)
        out = kern(*args)
        with jax.default_matmul_precision("highest"):
            want = ref.paged_attention_ref(*args, bits=bits)
        check_close(f"paged_attention_pallas kv{bits} tpu_custom_call=yes",
                    out, want)
        require(not np.asarray(out)[-1].any(), "dead row must flush zeros")
    for bits in (16, 8):
        # every row live: the gather oracle has no zero-flush for dead rows
        q, kp, vp, ks, vs, tidx, bt, pos = pool_case(
            rng, (5, 16, 21, 100, 200, 256), bits, w=W)
        args = (q, kp, vp, ks, vs, tidx, bt, pos)

        def kern(*a, bits=bits):
            return paged_attention_pallas_multi(*a, bits=bits)

        kernel_hlo(kern, *args)
        out = kern(*args)
        cache = PagedKVCache(kp, vp, ks[:, 0], vs[:, 0], tidx, bt, bits=bits)
        b = q.shape[0]
        with jax.default_matmul_precision("highest"):
            want = decode_attention_window(
                q.reshape(b, W, HKV * HG, D), paged_view(cache), pos, ks, vs)
        check_close(f"paged_attention_pallas_multi kv{bits} W={W} "
                    f"tpu_custom_call=yes", out.reshape(want.shape), want)


def check_server() -> None:
    compile_s = [0.0]

    def on_event(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    args = serve.parser().parse_args(
        ["--full", "--continuous", "--kv-bits", "16",
         "--paged-backend", "auto"])
    t0 = time.perf_counter()
    srv = serve.build_server(args)
    cfg = srv.cfg
    require((cfg.name, cfg.n_layers, cfg.d_model) == ("granite-3-2b", 40, 2048),
            f"full-width granite-3-2b, got {cfg.name}")
    require(srv.paged_backend == "pallas", srv.paged_backend)
    leaves = jax.tree.leaves((srv.params, srv._prequant))
    log(f"server built in {time.perf_counter() - t0:.1f}s: {cfg.name} "
        f"{cfg.n_layers} layers d_model {cfg.d_model}, backend "
        f"{srv.paged_backend}, resident weights "
        f"{sum(x.nbytes for x in leaves) / 1e9:.2f} GB")
    sched = ContinuousScheduler(srv, quantum=args.quantum)
    rng = np.random.default_rng(args.seed)
    reqs = [Request(tokens=rng.integers(0, cfg.vocab, int(n)).astype(np.int32),
                    max_new=MAX_NEW)
            for n in rng.integers(32, 193, N_REQ)]
    t1 = time.perf_counter()
    for r in reqs:
        sched.submit(r)
    while sched.step():
        pass
    wall = time.perf_counter() - t1
    results = [sched.results[i] for i in range(len(reqs))]
    for i, res in enumerate(results):
        require(res["status"] == RequestStatus.COMPLETED, (i, res["status"]))
        toks = np.asarray(res["tokens"])
        require(toks.shape == (MAX_NEW,), (i, toks.shape))
        require(((toks >= 0) & (toks < cfg.vocab)).all(), i)
    faults = sched.robustness_stats()["faults_detected"]
    require(faults == 0, faults)
    sched.check()
    seg_hlo = srv._segment.lower(
        jnp.zeros((args.quantum,), jnp.int32), sched._tok, sched._pos,
        sched._caches, jnp.zeros((sched.n_slots,), jnp.int32),
        jnp.full((sched.n_slots,), -1, jnp.int32)).as_text()
    require("tpu_custom_call" in seg_hlo, "decode segment has no TPU kernel")
    n_tok = sum(len(r["tokens"]) for r in results)
    lens = sorted(len(r.tokens) for r in reqs)
    log(f"requests: {N_REQ}/{N_REQ} COMPLETED with {MAX_NEW} tokens each, "
        f"prompt lengths {lens}, ids in [0, {cfg.vocab}), faults_detected 0, "
        f"block-pool audit clean, decode segment has tpu_custom_call")
    log(f"smoke timing (not a benchmark): {n_tok} tokens, drain wall "
        f"{wall:.1f}s, backend compile {compile_s[0]:.1f}s over the server "
        f"build and drain")


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    cache_dir = enable_compile_cache()
    log(f"device_kind {dev.device_kind}, {len(jax.devices())} device(s), "
        f"compile cache {cache_dir}")
    t0 = time.perf_counter()
    check_kernels()
    log(f"kernel checks passed in {time.perf_counter() - t0:.1f}s")
    check_server()
    stats = dev.memory_stats()
    log(f"peak_bytes_in_use {stats['peak_bytes_in_use']} of bytes_limit "
        f"{stats['bytes_limit']}")
    require(stats["peak_bytes_in_use"] < stats["bytes_limit"],
            "peak bytes below the chip's limit")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
