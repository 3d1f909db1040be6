"""Serving launcher — the paper's deployment scenario: an adaptive inference
engine behind a Profile Manager with an energy budget.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
      --requests 6 --budget-inferences 200 [--kv-bits 8] [--full]
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import ARCHS, get_config, get_smoke
from repro.core.energy import TPU_V5E, activity_factor, step_energy
from repro.core.engine import AdaptiveEngine, QuantIndex
from repro.core.manager import ProfileManager, ProfileStats
from repro.core.profiles import PAPER_PROFILES, paper_profiles
from repro.models import transformer as T
from repro.runtime import compute_dtype, enable_compile_cache
from repro.serving.engine import AdaptiveServer, Request, ServingConfig


def profile_stats(cfg, profs, n_params: int) -> list[ProfileStats]:
    """Modeled per-inference energy per profile (roofline §energy model);
    accuracies are the paper's Table-1 shape (calibration hook in prod)."""
    acc_by_w = {8: 0.989, 4: 0.953, 32: 0.998}
    out = []
    t_est = 2.0 * n_params / TPU_V5E.peak_flops  # one fwd, compute term
    for p in profs:
        a, w = next(iter(p.bits.values()))
        act = activity_factor(min(a, 16), min(w, 16), min(w, 16) / 16.0)
        name_acc = acc_by_w.get(w, 0.97) - (0.004 if p.name == "Mixed" else 0)
        out.append(ProfileStats(p.name, name_acc,
                                step_energy(t_est, act), t_est))
    return out


def init_masters(cfg, seed: int) -> dict:
    """Seeded random parameters with the float matmul masters (``w``
    leaves) held in the compute dtype: bf16 on TPU halves their resident
    bytes; f32 on CPU leaves them as ``init_params`` makes them. Built in one
    jit, so no float32 copy of the whole model is ever resident."""
    cd = compute_dtype()

    def init(key):
        return jax.tree_util.tree_map_with_path(
            lambda path, a: a.astype(cd) if path[-1].key == "w" else a,
            T.init_params(cfg, key))

    return jax.jit(init)(jax.random.PRNGKey(seed))


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b", choices=ARCHS)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--kv-bits", type=int, default=16, choices=[4, 8, 16],
                    help="KV-cache precision: 16 = bf16, 8 = int8, 4 = "
                         "packed int4 (two tokens' nibbles per byte — half "
                         "the pool bytes of kv8, 2x the token capacity at "
                         "equal block count)")
    ap.add_argument("--precision-policy", default=None, metavar="PATH",
                    help="per-layer KV bit-width policy JSON (written by "
                         "benchmarks/precision_frontier.py): profile 0 — "
                         "the accuracy-critical binding — pins the all-"
                         "high row, every other profile rides the searched "
                         "frontier schedule. The [n_profiles, n_layers] "
                         "table is data to the jitted decode (no retrace "
                         "on profile switches)")
    ap.add_argument("--budget-inferences", type=float, default=200,
                    help="energy budget in units of full-power inferences")
    ap.add_argument("--continuous", action="store_true",
                    help="serve through the continuous-batching slot pool "
                         "(ContinuousScheduler) instead of static groups")
    ap.add_argument("--quantum", type=int, default=8,
                    help="decode steps per continuous-batching segment")
    ap.add_argument("--paged-kv", dest="paged_kv", action="store_true",
                    default=True,
                    help="paged KV pool for the continuous scheduler: "
                         "global block pool + per-row block tables "
                         "(default)")
    ap.add_argument("--no-paged-kv", dest="paged_kv", action="store_false",
                    help="contiguous [max_batch, slots] KV rows instead of "
                         "the paged pool")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block of the paged pool "
                         "(default: 16)")
    ap.add_argument("--pool-blocks", type=int, default=None,
                    help="physical KV blocks to provision; default sizes "
                         "the pool at the contiguous footprint — set lower "
                         "to oversubscribe (admission backpressure kicks "
                         "in when it runs dry)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false", default=True,
                    help="disable shared-prefix reuse (block-hash registry "
                         "+ suffix-only admission prefill)")
    ap.add_argument("--paged-backend", default="auto",
                    choices=["auto", "pallas", "gather"],
                    help="paged decode backend: 'pallas' attends in place "
                         "against the block pool through the paged-"
                         "attention kernel (no dense view, no fold-back); "
                         "'gather' materializes the per-segment view (the "
                         "oracle path); 'auto' = pallas on TPU, gather "
                         "elsewhere (default)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill: admission prompts longer than "
                         "this many tokens prefill in block-aligned chunks "
                         "interleaved with decode segments (full-causal "
                         "stacks; default: disabled)")
    ap.add_argument("--priority-classes", type=int, default=1,
                    help="request priority classes for the continuous "
                         "scheduler: 1 = classless FIFO (default); >=2 "
                         "builds the critical/.../saver ladder — class 0 "
                         "admits first and is profile-bound to the "
                         "accuracy target (every 3rd demo request rides "
                         "class 0, the rest the lowest class)")
    ap.add_argument("--preemption", action="store_true",
                    help="arm preemptive scheduling: a critical arrival "
                         "that cannot admit evicts saver-class rows (block "
                         "tables + host KV masters snapshotted; they "
                         "resume bit-exactly through the continuation-"
                         "prefill executable). Requires --continuous, the "
                         "paged pool, and a full-causal stack")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request completion deadline in wall-clock ms "
                         "from submission; queued requests past (or "
                         "provably unable to meet) their deadline finalize "
                         "EXPIRED, live rows are reaped at the next flush "
                         "boundary. Requires --continuous")
    ap.add_argument("--shed", type=int, default=None, metavar="DEPTH",
                    help="graceful overload degradation: when the queue "
                         "exceeds DEPTH (or the predicted deadline-miss "
                         "count exceeds it), the lowest-priority tail "
                         "request finalizes SHED instead of queuing. "
                         "Requires --continuous")
    ap.add_argument("--inject-faults", action="store_true",
                    help="arm the seeded chaos schedule: random NaN-logit "
                         "injections into live decode rows (detected by "
                         "the in-segment finite check; the row is "
                         "quarantined and retried at a higher-accuracy "
                         "profile), plus one allocator-drought admission "
                         "round. Requires --continuous")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the deterministic fault schedule "
                         "(default: 0; only with --inject-faults)")
    ap.add_argument("--retry-budget", type=int, default=2,
                    help="max quarantine retries per request before it "
                         "finalizes FAILED (default: 2)")
    ap.add_argument("--speculate", action="store_true",
                    help="speculative decoding: each decode step drafts "
                         "--draft-k tokens (self-speculative n-gram "
                         "lookup) and verifies the whole window in one "
                         "batched pass — token-identical to greedy, "
                         "faster on predictable streams. Requires "
                         "--continuous")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="drafted tokens per speculative window "
                         "(window = draft-k + 1 positions; default: 4)")
    ap.add_argument("--draft-model", default=None,
                    help="external drafter from the registry instead of "
                         "the self-speculative n-gram lookup (e.g. "
                         "'repeat'; default: self-speculative)")
    ap.add_argument("--journal-dir", default=None, metavar="DIR",
                    help="crash-consistent serving: write-ahead request "
                         "journal + live-state checkpoints under DIR. On "
                         "boot, a non-empty DIR is recovered first — the "
                         "newest committed checkpoint restores live/queued "
                         "state, the journal suffix replays, and every "
                         "accepted request resumes token-identically "
                         "(docs/serving.md §Durability). Requires "
                         "--continuous")
    ap.add_argument("--checkpoint-every", type=int, default=8,
                    metavar="ROUNDS",
                    help="live-state checkpoint cadence in scheduler "
                         "rounds (default: 8; 0 = journal only — nothing "
                         "is lost either way, a checkpoint just bounds "
                         "recovery recompute). Only with --journal-dir")
    ap.add_argument("--drain-on-sigterm", action="store_true",
                    help="graceful shutdown: SIGTERM stops admission, "
                         "runs every admitted row to a terminal status, "
                         "writes a final checkpoint (with --journal-dir) "
                         "and exits; queued requests stay journaled for "
                         "the next process. Requires --continuous")
    ap.add_argument("--kv16-masters", action="store_true",
                    help="keep f32 KV masters for shared/chunked rows even "
                         "at --kv-bits 16 (structurally bit-exact "
                         "continuations + exact kv16 checkpoints; costs "
                         "host memory)")
    ap.add_argument("--aging", type=int, default=None, metavar="ROUNDS",
                    help="anti-starvation promotion: a queued request "
                         "that has waited ROUNDS scheduler rounds at the "
                         "head of its class climbs one priority level "
                         "(position only — profile binding and billing "
                         "keep the submitted class). Default: off = "
                         "strict lowest-level-first")
    ap.add_argument("--paranoid", action="store_true",
                    help="run the full block-pool invariant audit "
                         "(refcounts vs free/LRU/live partition, "
                         "BlockAllocator.check) after every scheduler "
                         "step. Requires --continuous")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def build_server(args: argparse.Namespace) -> AdaptiveServer:
    """The server ``main`` runs, from its parsed command line: seeded
    random weights (:func:`init_masters`), the six paper profiles behind a
    budget :class:`ProfileManager`, and the :class:`ServingConfig` the
    options describe."""
    cfg = get_config(args.arch) if args.full else get_smoke(args.arch)
    if not cfg.causal:
        raise SystemExit("encoder-only arch has no decode step")
    if args.preemption and not args.continuous:
        raise SystemExit("--preemption needs --continuous (the slot pool)")
    if not args.continuous and (args.deadline_ms is not None
                                or args.shed is not None
                                or args.inject_faults or args.paranoid):
        raise SystemExit("--deadline-ms/--shed/--inject-faults/--paranoid "
                         "need --continuous (the fault-tolerant scheduler)")
    if args.speculate and not args.continuous:
        raise SystemExit("--speculate needs --continuous (draft/verify "
                         "windows run through the slot-pool segment)")
    if (args.journal_dir or args.drain_on_sigterm) and not args.continuous:
        raise SystemExit("--journal-dir/--drain-on-sigterm need --continuous "
                         "(durability hooks live on the slot-pool scheduler)")
    policy = None
    if args.precision_policy:
        import json
        with open(args.precision_policy) as f:
            pp = json.load(f)
        row = tuple(int(b) for b in pp["schedule"])
        if len(row) != cfg.n_layers:
            raise SystemExit(f"--precision-policy schedule has {len(row)} "
                             f"layers, model has {cfg.n_layers}")
        # profile 0 is the accuracy-critical binding: pin it to the exact
        # all-high row; the rest ride the searched frontier schedule
        policy = tuple((16,) * cfg.n_layers if i == 0 else row
                       for i in range(len(PAPER_PROFILES)))
    params = init_masters(cfg, args.seed)
    names = T.quant_layer_names(cfg)
    profs = paper_profiles(names, inner_layers=[])
    engine = AdaptiveEngine(tuple(profs), QuantIndex(names),
                            lambda p, br, b: T.train_loss(p, cfg, br, b))
    stats = profile_stats(cfg, profs, T.param_count(params))
    mgr = ProfileManager(stats, accuracy_target=0.985, accuracy_floor=0.95,
                         budget_j=stats[0].energy_j * args.budget_inferences,
                         low_energy=0.5)
    return AdaptiveServer(cfg, params, engine,
                          ServingConfig(slots=256, kv_bits=args.kv_bits,
                                        max_batch=4, paged_kv=args.paged_kv,
                                        block_size=args.block_size,
                                        pool_blocks=args.pool_blocks,
                                        prefix_cache=args.prefix_cache,
                                        paged_backend=args.paged_backend,
                                        prefill_chunk=args.prefill_chunk,
                                        priority_classes=args.priority_classes,
                                        preemption=args.preemption,
                                        aging=args.aging,
                                        speculate=args.speculate,
                                        draft_k=args.draft_k,
                                        draft_model=args.draft_model,
                                        kv16_masters=args.kv16_masters,
                                        precision_policy=policy),
                          manager=mgr)


def main() -> None:
    args = parser().parse_args()
    enable_compile_cache()
    stop = {"drain": False}
    if args.drain_on_sigterm:
        # install before the (slow) model/executable build: a TERM during
        # warmup drains at the first step boundary instead of killing us
        import signal
        signal.signal(signal.SIGTERM, lambda *_: stop.update(drain=True))
    srv = build_server(args)
    cfg, mgr = srv.cfg, srv.manager
    rng = np.random.default_rng(args.seed)
    n_cls = max(1, args.priority_classes)
    reqs = [Request(tokens=rng.integers(0, cfg.vocab, int(n)).astype(np.int32),
                    max_new=args.max_new,
                    accuracy_critical=(i % 3 == 0),
                    priority=(0 if i % 3 == 0 else n_cls - 1),
                    deadline_ms=args.deadline_ms)
            for i, n in enumerate(rng.integers(4, 24, args.requests))]
    import time
    t0 = time.perf_counter()
    sched = None
    if args.continuous:
        from repro.serving.faults import FaultSchedule
        from repro.serving.policy import ShedPolicy
        from repro.serving.scheduler import ContinuousScheduler
        faults = None
        if args.inject_faults:
            # one guaranteed recoverable fault (request 1, first attempt)
            # plus random NaNs at ~1 per 4 requests (capped) and one
            # drought round — every injection detected, quarantined, and
            # retried at a higher-accuracy profile under --retry-budget
            faults = FaultSchedule(args.fault_seed, p_nan=0.25,
                                   max_nan=max(1, args.requests // 4),
                                   nan_at={min(1, args.requests - 1): (0,)},
                                   alloc_at=(2,))
        sched_kwargs = dict(
            quantum=args.quantum,
            shed=(ShedPolicy(max_queue=args.shed)
                  if args.shed is not None else None),
            faults=faults, retry_budget=args.retry_budget,
            paranoid=args.paranoid)
        if args.journal_dir:
            from repro.serving.durability import recover
            sched = recover(srv, args.journal_dir,
                            checkpoint_every=args.checkpoint_every,
                            **sched_kwargs)
            ri = sched.recover_info
            if ri["resumed_rows"] or ri["chunk_rows"] or ri["replayed"]:
                print(f"[serve] recovered from {args.journal_dir}: "
                      f"{ri['resumed_rows']} live rows resumable, "
                      f"{ri['chunk_rows']} mid-prompt chunk rows rebuilt, "
                      f"{ri['replayed']} journal records replayed, "
                      f"{len(ri['refilled'])} re-prefilled after corruption "
                      f"({ri['recovery_s']*1e3:.0f} ms)")
        else:
            sched = ContinuousScheduler(srv, **sched_kwargs)
        for r in reqs:
            sched.submit(r)
        drained = False
        while sched.step():
            if stop["drain"]:
                # graceful shutdown: finish every admitted row, leave the
                # queue journaled for the next process, cut one final
                # checkpoint, exit 0
                sched.drain()
                if sched.durable is not None:
                    sched.durable.checkpoint()
                drained = True
                break
        results = [sched.results.get(i) for i in range(sched._n)]
        if drained:
            print(f"[serve] SIGTERM drain: {sched.pending} request(s) left "
                  f"queued (journaled) after finishing all admitted rows")
    else:
        results = srv.serve(reqs)
    wall = time.perf_counter() - t0
    if sched is not None and sched.paged:
        st = sched.paged_stats()
        print(f"[serve] paged KV: peak {st['peak_used_blocks']}/"
              f"{st['pool_blocks']} blocks of {st['block_size']} tokens, "
              f"prefix hits {st.get('registry_hits', 0)}, "
              f"lru cached {st['lru_cached_blocks']}, "
              f"preemptions {st['preemptions']} "
              f"(resumed {st['resumes']})")
    n_tok = sum(len(r["tokens"]) for r in results if r)
    for i, r in enumerate(results):
        if r is None:                # still queued after a SIGTERM drain
            print(f"[serve] req{i}: queued (journaled for next process)")
            continue
        status = r.get("status")
        extra = "" if status is None else f" [{status.value}" + (
            f": {r['reason']}]" if r.get("reason") else "]")
        retries = r.get("retries", 0)
        if retries:
            extra += f" (recovered after {retries} escalated "\
                     f"retr{'y' if retries == 1 else 'ies'})"
        print(f"[serve] req{i}: {len(r['tokens'])} tokens, "
              f"profiles used: {sorted(set(r['profile_trace']))}{extra}")
    if sched is not None and (args.inject_faults or args.shed is not None
                              or args.deadline_ms is not None
                              or args.paranoid):
        rs = sched.robustness_stats()
        print(f"[serve] robustness: cancelled={rs['cancelled']} "
              f"expired={rs['expired']} shed={rs['shed']} "
              f"failed={rs['failed']} recovered={rs['recovered']} "
              f"faults_detected={rs['faults_detected']}")
        sched.check()    # full pool audit (raises on any leak)
        print("[serve] block-pool audit clean: refcounts, free list, and "
              "LRU partition the pool exactly")
    print(f"[serve] {n_tok} tokens in {wall:.2f}s "
          f"({n_tok / wall:.0f} tok/s incl. compile; fused decode loop)")
    print(f"[serve] energy spent: {mgr.spent_j:.2e} J "
          f"({100*(1-mgr.remaining_fraction()):.0f}% of budget), "
          f"saver_mode={mgr._saver}")


if __name__ == "__main__":
    main()
