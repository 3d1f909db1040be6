import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count="
                           + os.environ.get("REPRO_DRYRUN_DEVICES", "512")).strip()
# ^ MUST run before any jax import: jax locks the device count on first init.

"""Multi-pod dry-run driver (brief §MULTI-POD DRY-RUN).

For every (architecture × input shape × mesh) cell:
``jax.jit(step, in_shardings, out_shardings).lower(...).compile()`` on the
production mesh built from placeholder CPU devices, then record
``memory_analysis()`` / ``cost_analysis()`` and the collective byte totals
parsed from the post-SPMD HLO into a JSON artifact that §Roofline reads.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-2b \
      --shape train_4k --mesh pod1 [--native-bits 8] [--kv-bits 8] \
      [--out artifacts/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh pod1 pod2
"""
import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.configs import ARCHS, SHAPES, get_config, shape_applicable
from repro.launch import sharding as shd
from repro.launch import specs as S
from repro.launch.mesh import dp_size, make_production_mesh, make_tiny_mesh

# bf16 compute in the lowered HLO (TPU target numerics); never executed here.
# Applied inside run_cell/main — NOT at import, so importing this module for
# its parsers (tests) doesn't poison CPU-executing code with bf16 dots.

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _tensor_bytes(shape_str: str) -> int:
    """'bf16[8,128]' → 2048. Tuple shapes handled by summing members."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_LINE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.-]+\s*=\s*"
    r"((?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\]\S*))\s+([a-z0-9-]+)\(")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:  # iota form [num_groups, group_size]<=[...]
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def collective_bytes(hlo_text: str) -> dict:
    """Sum *operand* bytes of every collective in the post-SPMD HLO.

    Operand shapes are not always printed, so bytes derive from the (always
    printed) result shape and the collective's semantics:
    all-gather operand = result / group_size; reduce-scatter operand =
    result × group_size; all-reduce / all-to-all / collective-permute
    operand = result. Async ``*-done`` halves are skipped (their ``*-start``
    twin carries the shape).
    """
    per_kind = {k: 0 for k in _COLLECTIVES}
    count = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _LINE_RE.match(line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        if op.endswith("-done"):
            continue
        kind = next((k for k in _COLLECTIVES if op.startswith(k)), None)
        if kind is None:
            continue
        nbytes = _tensor_bytes(shape_str)
        if kind == "all-gather":
            nbytes //= max(1, _group_size(line))
        elif kind == "reduce-scatter":
            nbytes *= _group_size(line)
        per_kind[kind] += nbytes
        count[kind] += 1
    total = sum(per_kind.values())
    return {"total": total, "per_kind": per_kind, "count": count}


def _mesh_for(name: str):
    if name == "pod1":
        return make_production_mesh(multi_pod=False)
    if name == "pod2":
        return make_production_mesh(multi_pod=True)
    if name == "tiny":
        return make_tiny_mesh(2, 2)
    if name == "tiny2":
        return make_tiny_mesh(2, 2, multi_pod=True)
    raise ValueError(name)


def _lower_step(cfg, shape, mesh, *, native_bits, kv_bits, serve_layout=False):
    """Lower the cell's step function with explicit in/out shardings."""
    engine = S.build_engine(cfg)
    pid_sh = shd.named(mesh, jax.sharding.PartitionSpec())
    pid = jax.ShapeDtypeStruct((), jnp.int32)
    if shape.kind == "train":
        params = S.abstract_params(cfg)
        opt = S.abstract_opt(params)
        batch = S.input_specs(cfg, shape)
        p_sh = shd.named(mesh, shd.param_specs(params, mesh))
        opt_sh = type(opt)(step=pid_sh,
                           mu=shd.named(mesh, shd.param_specs(opt.mu, mesh)),
                           nu=shd.named(mesh, shd.param_specs(opt.nu, mesh)))
        b_sh = shd.named(mesh, shd.batch_specs(batch, mesh))
        fn = S.make_train_step_fn(cfg, engine)
        jitted = jax.jit(fn, in_shardings=(p_sh, opt_sh, pid_sh, b_sh),
                         out_shardings=(p_sh, opt_sh, None),
                         donate_argnums=(0, 1))
        return jitted.lower(params, opt, pid, batch)
    if shape.kind == "prefill":
        params = S.abstract_params(cfg, native_bits=native_bits)
        batch = S.input_specs(cfg, shape)
        p_sh = shd.named(mesh, shd.param_specs(params, mesh, serve=serve_layout))
        b_sh = shd.named(mesh, shd.batch_specs(batch, mesh))
        fn = S.make_prefill_fn(cfg, engine)
        jitted = jax.jit(fn, in_shardings=(p_sh, pid_sh, b_sh))
        return jitted.lower(params, pid, batch)
    # decode
    params = S.abstract_params(cfg, native_bits=native_bits)
    caches = S.abstract_caches(cfg, shape, kv_bits=kv_bits)
    io = S.input_specs(cfg, shape)
    p_sh = shd.named(mesh, shd.param_specs(params, mesh, serve=serve_layout))
    c_sh = shd.named(mesh, shd.cache_specs(caches, mesh))
    i_sh = shd.named(mesh, shd.batch_specs(io, mesh))
    fn = S.make_decode_fn(cfg, engine)
    jitted = jax.jit(fn, in_shardings=(p_sh, pid_sh, i_sh["tokens"],
                                       i_sh["pos"], c_sh),
                     out_shardings=(None, c_sh), donate_argnums=(4,))
    return jitted.lower(params, pid, io["tokens"], io["pos"], caches)


def _measure(compiled) -> dict:
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    def _get(o, *names):
        for n in names:
            v = getattr(o, n, None) if not isinstance(o, dict) else o.get(n)
            if v is not None:
                return v
        return None

    return dict(
        flops=float(cost.get("flops", 0.0)),
        bytes_accessed=float(cost.get("bytes accessed", 0.0)),
        memory=dict(
            argument_bytes=_get(mem, "argument_size_in_bytes"),
            output_bytes=_get(mem, "output_size_in_bytes"),
            temp_bytes=_get(mem, "temp_size_in_bytes"),
        ),
        collectives=coll,
        hlo_lines=hlo.count("\n"),
    )


def _extrapolate(a1: dict, a2: dict, n_layers: int) -> dict:
    """Exact depth extrapolation from unrolled L=1 / L=2 measurements:
    per_layer = m(2) − m(1); total(L) = m(1) + (L−1)·per_layer.

    cost_analysis counts while-loop bodies once (verified in
    EXPERIMENTS §Dry-run-method), so the production scanned lowering
    under-reports; the unrolled variants have loop-free depth, making the
    linear-in-L fit exact for flops / bytes / collective bytes.
    """
    out = {}
    for key in ("flops", "bytes_accessed"):
        m1, m2 = a1[key], a2[key]
        per = max(0.0, m2 - m1)
        out[key] = m1 + (n_layers - 1) * per
        out[key + "_per_layer"] = per
    c1, c2 = a1["collectives"], a2["collectives"]
    per_kind = {}
    for k in c1["per_kind"]:
        per = max(0, c2["per_kind"][k] - c1["per_kind"][k])
        per_kind[k] = c1["per_kind"][k] + (n_layers - 1) * per
    out["collective_bytes"] = {"total": sum(per_kind.values()),
                               "per_kind": per_kind}
    return out


def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             native_bits: int | None, kv_bits: int,
             remat: bool | None = None, analysis: bool = True,
             constraints: bool = False, swa_skip: bool = True,
             remat_policy: str = "nothing", serve_layout: bool = False,
             config_edit=None, verbose: bool = True) -> dict:
    from repro.launch.mesh import dp_axes
    from repro.models import pshard

    runtime.set_compute_dtype(jnp.bfloat16)  # TPU-target numerics in the HLO
    shape = SHAPES[shape_name]
    cfg0 = get_config(arch)
    ok, why = shape_applicable(cfg0, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "native_bits": native_bits, "kv_bits": kv_bits,
           "opts": {"constraints": constraints, "swa_skip": swa_skip,
                    "remat_policy": remat_policy, "serve_layout": serve_layout}}
    if not ok:
        rec.update(status="skipped", reason=why)
        if verbose:
            print(f"[dryrun] {arch} × {shape_name}: SKIP ({why})")
        return rec

    mesh = _mesh_for(mesh_name)
    cfg = S.adapt_config(cfg0, shape, dp_size(mesh))
    cfg = dataclasses.replace(cfg, swa_block_skip=swa_skip,
                              remat_policy=remat_policy)
    if remat is not None:
        cfg = dataclasses.replace(cfg, remat=remat)
    if config_edit is not None:
        cfg = config_edit(cfg)

    if constraints:
        dp = dp_axes(mesh)
        pshard.enable(mesh, dp[0] if len(dp) == 1 else dp)
    else:
        pshard.disable()

    with mesh:
        # --- production lowering: full depth, scan-over-layers ---
        t0 = time.time()
        lowered = _lower_step(cfg, shape, mesh, native_bits=native_bits,
                              kv_bits=kv_bits, serve_layout=serve_layout)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        prod = _measure(compiled)
        del compiled, lowered

        # --- analysis lowerings: depth-unrolled L=1 / L=2 → exact totals ---
        if analysis:
            meas = []
            for L in (1, 2):
                cfg_l = dataclasses.replace(cfg, n_layers=L, scan_layers=False,
                                            unroll_inner=True)
                c = _lower_step(cfg_l, shape, mesh, native_bits=native_bits,
                                kv_bits=kv_bits,
                                serve_layout=serve_layout).compile()
                meas.append(_measure(c))
                del c
            rec["analysis"] = _extrapolate(meas[0], meas[1], cfg.n_layers)
    pshard.disable()

    rec.update(
        status="ok",
        devices=int(np.prod(list(mesh.shape.values()))),
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        production=prod,
    )
    if verbose:
        a = rec.get("analysis", {})
        flops = a.get("flops", prod["flops"]) or 0.0
        print(f"[dryrun] {arch} × {shape_name} × {mesh_name}: OK "
              f"(lower {t_lower:.1f}s, compile {t_compile:.1f}s, "
              f"flops/dev {flops:.3e}, "
              f"coll/dev {a.get('collective_bytes', prod['collectives'])['total']/2**30:.2f} GiB)")
        print("  memory_analysis:", prod["memory"])
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", nargs="+", default=["pod1"],
                    choices=["pod1", "pod2", "tiny", "tiny2"])
    ap.add_argument("--all", action="store_true",
                    help="run every applicable (arch × shape) cell")
    ap.add_argument("--native-bits", type=int, default=None,
                    help="serve paths: native int weight storage (8 or 4)")
    ap.add_argument("--kv-bits", type=int, default=16, choices=[4, 8, 16])
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--no-analysis", action="store_true",
                    help="skip the L=1/L=2 unrolled roofline lowerings")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--constraints", action="store_true",
                    help="enable activation-sharding constraints (§Perf)")
    ap.add_argument("--no-swa-skip", action="store_true",
                    help="baseline masked attention for SWA archs")
    ap.add_argument("--remat-policy", default="nothing",
                    choices=["nothing", "dots"])
    ap.add_argument("--serve-layout", action="store_true",
                    help="pure-TP weight layout for serving (no FSDP gathers)")
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch, shape in cells:
        for mesh_name in args.mesh:
            tag = f"{arch}__{shape}__{mesh_name}"
            if args.native_bits:
                tag += f"__w{args.native_bits}"
            if args.kv_bits != 16:
                tag += f"__kv{args.kv_bits}"
            if args.tag:
                tag += f"__{args.tag}"
            path = os.path.join(args.out, tag + ".json")
            if args.skip_existing and os.path.exists(path):
                with open(path) as f:
                    if json.load(f).get("status") in ("ok", "skipped"):
                        print(f"[dryrun] {tag}: cached, skip")
                        continue
            try:
                rec = run_cell(arch, shape, mesh_name,
                               native_bits=args.native_bits,
                               kv_bits=args.kv_bits,
                               remat=False if args.no_remat else None,
                               constraints=args.constraints,
                               swa_skip=not args.no_swa_skip,
                               remat_policy=args.remat_policy,
                               serve_layout=args.serve_layout,
                               analysis=(mesh_name in ("pod1", "tiny")
                                         and not args.no_analysis))
            except Exception as e:  # a failing cell is a bug — surface it
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                       "status": "error", "error": f"{type(e).__name__}: {e}"}
                failures.append(tag)
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(rec, f, indent=1)
    if failures:
        raise SystemExit(f"dry-run FAILURES: {failures}")
    print("[dryrun] all requested cells OK")


if __name__ == "__main__":
    main()
