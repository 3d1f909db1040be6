"""Continuous-batching execution core over the fused decode scan.

The paper's runtime (§4.4, Fig. 4) is an adaptive inference engine that keeps
serving under a shifting energy budget — which presumes the serving layer
keeps the device *busy* under real, heterogeneous traffic. Static grouped
``serve()`` can't: a group must finish entirely before the next one starts, so
every finished row burns decode steps as dead padding and every queued request
waits for the whole group. This module replaces that with continuous batching.

Since the policy refactor this class is the **execution core** only: it owns
wave dispatch, segment running, flush, and the paged-block bookkeeping.
*Which* request admits next, which class binds which profile, and who gets
preempted for whom live in :mod:`repro.serving.policy`; the physical block
economy (refcounts, the retired-block LRU, prefix registration) lives in
:mod:`repro.serving.paged`.

**Slot pool.** The scheduler owns a fixed ``[max_batch]`` row pool whose
decode state (last token, position, KV/SSM caches) lives on device and is
threaded through *donated* jit boundaries — the pool buffers are updated in
place, never copied. A request occupies one row from admission to retirement;
free rows idle with ``remaining == 0`` (the done-mask freezes them, and MoE
capacity dispatch drops them via ``row_valid``).

**Segment quantum.** Decode runs in fixed-size segments of
:func:`repro.models.transformer.decode_segment` — ``quantum`` scan steps per
dispatch, all shapes static in ``(max_batch, quantum)``, so every segment of
the server's lifetime reuses ONE compiled executable no matter which rows are
live. The quantum is the admission latency knob: between segments, retired
rows are refilled from the policy queue by an *admission wave* — one ragged
prefill of every waiting request (rows bucketed to a power of two, prompts
left-padded to a power-of-two length bucket with ``prompt_len`` riding as
data → compile count log² rather than one executable per shape) whose
first tokens are argmaxed on device and whose cache rows are scattered into
the free slots, all inside a single donated dispatch. Token blocks come back
*asynchronously*: retirement and admission decisions need only host-side
``remaining`` counts, so the engine loop dispatches the next segment before
materializing the previous one's tokens (``_flush(keep=1)``) and host-side
scheduling overlaps device compute.

**Paged KV.** By default the pool's attention cache is *paged* (a global
block pool + per-row block tables — :mod:`repro.serving.paged` holds the
host-side allocator and shared-prefix registry, ``docs/serving.md`` the full
design): a row holds only the blocks its ``prompt + max_new`` actually
touch instead of a whole ``[slots]`` reservation, hash-matched prompt
prefixes are admitted with a suffix-only prefill against blocks that are
mapped rather than recomputed and re-stored, and a dry allocator turns into
queue backpressure — or, under a preemptive policy, a preemption decision —
rather than corruption.

**Preemption.** With :class:`ServingConfig.preemption`, an urgent arrival
that cannot admit evicts policy-chosen victim rows: :meth:`evict_row`
flushes, snapshots the victim's block table + host-side KV masters
(:class:`~repro.serving.paged.RowSnapshot`), releases its blocks (registered
prefixes park in the allocator's retired-block LRU), unmaps its table, and
requeues it at the front of its class. The suspended row later *resumes*
through the existing continuation-prefill executable — its whole written
span replayed as the prefix with an empty suffix, pure data movement that
rebuilds cache bytes, scales and carry **bit-exactly** — so a resumed row
continues token-identically to an uninterrupted run by construction, at
kv16 and kv8, shared-CoW rows included. An admission
round dispatches at most TWO prefill waves (cold / shared / resume /
chunk-continuation — an over-budget kind waits a round; imminent chunk
continuations pre-commit their share), and every decode segment still
runs the one
pool-lifetime ``_segment`` executable; ``tests/test_scheduler_policy.py``
guards both.

**Why re-planning per segment keeps the ledger exact.** The
:class:`ProfileManager` policy is deterministic given its energy ledger, so
profile ids can be precomputed as data — but only as far ahead as the set of
live rows is known. A whole-generation schedule would bill rows that finish
(or get admitted) mid-flight. Planning exactly one segment ahead, with
:meth:`ProfileManager.plan_schedule_classes` over the *actual* per-row
remaining budgets and priority-class bindings, bills step ``i`` for precisely
the rows live at step ``i`` — the same ledger evolution as a per-step
select/account oracle (admission prefills are billed like the stepwise
engine bills prefill: one inference). Suspension and resume bill **nothing
new**: the resume wave recomputes a token the row already emitted (and was
billed for), so a request's total billed inferences are invariant under
preemption. Every billing event is recorded in
:attr:`ContinuousScheduler.events` so the tests can replay the ledger
against that oracle.

**Fault tolerance.** Every request leaves through exactly one terminal
:class:`~repro.serving.engine.RequestStatus` on its result dict:
``COMPLETED`` (all tokens delivered), ``CANCELLED`` (:meth:`cancel` — queued
requests drop immediately, live rows are reaped at the next flush boundary
so billed inferences equal delivered tokens exactly), ``EXPIRED``
(``Request.deadline_ms`` passed, or admission predicts — from the step-time
EMA — that the deadline is unreachable and rejects up front), ``SHED``
(a :class:`~repro.serving.policy.ShedPolicy` judged the pool overloaded at
submission), or ``FAILED`` (quarantine retries exhausted). A row caught
producing non-finite logits (the per-row finite-check rides the decode-scan
carry — see :func:`repro.models.transformer.decode_segment`) is
*quarantined*: its blocks are released through the same machinery as
:meth:`evict_row`, its poisoned tokens are discarded (argmax over NaN is
garbage — a retry must restart from the prompt to be token-identical to a
clean run), its profile binding escalates one rung toward the accuracy
target (``accuracy_critical=True``), and it re-queues at its class front
after an exponential backoff, up to ``retry_budget`` attempts. Injected
chaos (:class:`~repro.serving.faults.FaultSchedule`) and the audit
(:meth:`check`, the ``paranoid`` mode) make all of this testable
deterministically.

**Tracing.** The host path is named in ``jax.profiler`` spans, which a
running profiler records on the device trace's clock (and which cost a
few hundred nanoseconds each when none runs): ``serve.step`` (one round),
``serve.submit``, ``serve.admit``, ``serve.segment``, ``serve.flush`` with
``serve.flush.wait`` (the host blocked on the device's tokens) inside it,
and ``serve.dispatch.<fn>`` per executable call (the server's). A paged
pool also counts, per decode segment over its live rows, the KV blocks
the rows hold (``kv_blocks_reserved``) against those their contexts fill
(``kv_blocks_written``) and against their whole block tables
(``kv_blocks_table``, which a kernel visiting every logical block reads);
:meth:`ContinuousScheduler.paged_stats` returns all three.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation, annotate_function

from repro.models import transformer as T
from .engine import AdaptiveServer, Request, RequestStatus, _next_pow2
from .faults import FaultSchedule, Watchdog
from .paged import BlockAllocator, PrefixRegistry, RowSnapshot, prefix_keys
from .policy import RowState, SchedulingPolicy, ShedPolicy, make_policy

__all__ = ["ContinuousScheduler"]


class ContinuousScheduler:
    """Continuous batching on an :class:`AdaptiveServer`'s slot pool.

    ``quantum`` = decode steps per segment (admission latency vs dispatch
    overhead); ``prefill_bucket`` = minimum power-of-two prompt padding;
    ``policy`` = the :class:`~repro.serving.policy.SchedulingPolicy` that
    owns request ordering, class→profile binding and preemption (defaults
    to the one :func:`~repro.serving.policy.make_policy` derives from the
    server's :class:`ServingConfig` — the exact legacy FIFO unless
    ``priority_classes``/``preemption`` say otherwise).

    With ``ServingConfig.paged_kv`` (the default for attention stacks) the
    pool's KV state is *paged*: a global pool of fixed-size blocks plus
    per-row block tables (:class:`repro.models.attention.PagedKVCache`).
    Admission allocates exactly the blocks a request will touch
    (``ceil((prompt + max_new) / block_size)``, capped at the row's logical
    table) from a refcounted :class:`~repro.serving.paged.BlockAllocator`;
    retirement returns them — blocks a registered prefix still wants park
    in the allocator's retired-block LRU, where a later hash-matched
    admission resurrects them and real pressure reclaims them. When the
    allocator cannot satisfy the head of the policy queue, admission simply
    stops for this wave — queue backpressure, never corruption of a live
    row — unless a preemptive policy elects victims instead (a request that
    could never fit the whole pool is rejected at :meth:`submit`). With
    ``prefix_cache``, prompts are block-hashed at enqueue and matched
    against a :class:`~repro.serving.paged.PrefixRegistry` at admission:
    hits skip the prefix prefill entirely and (at kv16) map the registered
    blocks copy-on-write instead of re-storing them.
    """

    def __init__(self, server: AdaptiveServer, quantum: int = 8,
                 prefill_bucket: int = 8, record_events: bool = True,
                 policy: Optional[SchedulingPolicy] = None,
                 shed: Optional[ShedPolicy] = None,
                 faults: Optional[FaultSchedule] = None,
                 retry_budget: int = 2,
                 watchdog_s: Optional[float] = None,
                 clock: Optional[Callable[[], float]] = None,
                 paranoid: bool = False):
        """Build a scheduler (pool state + host bookkeeping) on ``server``.

        The jitted executables live on the server and are shared; the
        donated device pool (tok/pos/caches) and all queue/allocator/
        registry state are per-scheduler, so schedulers can be torn down
        and rebuilt without recompiling anything.

        Robustness knobs: ``shed`` enables graceful overload degradation
        (:class:`~repro.serving.policy.ShedPolicy` thresholds checked at
        :meth:`submit`); ``faults`` arms deterministic chaos injection
        (:class:`~repro.serving.faults.FaultSchedule`); ``retry_budget``
        bounds quarantine retries before ``FAILED``; ``watchdog_s`` arms
        the no-progress :class:`~repro.serving.faults.Watchdog` with that
        per-step budget; ``clock`` substitutes ``time.monotonic`` (tests
        inject virtual time to exercise deadlines without sleeping);
        ``paranoid`` runs the full :meth:`check` audit after every step.
        """
        self.srv = server
        self.quantum = int(quantum)
        self.bucket_min = int(prefill_bucket)
        # events/admission_log power the ledger-oracle and FIFO tests; a
        # long-lived server should pass record_events=False (they grow with
        # every segment step). Per-request state (prompt, result) is evicted
        # by poll_completed(); run() keeps results for its return value.
        self.record_events = record_events
        cfg, scfg = server.cfg, server.scfg
        nslots = self.n_slots = scfg.max_batch
        self.paged = bool(scfg.paged_kv) and cfg.has_attn
        self.policy = policy if policy is not None else make_policy(scfg)
        if self.policy.preemptive and (not self.paged or not scfg.preemption
                                       or server._admit_restore is None):
            raise ValueError(
                "a preemptive policy needs the paged pool and a server "
                "built with ServingConfig.preemption=True (the restore "
                "executable) on a supports_prefix_sharing stack")
        # device-resident pool state (donated through every jit below)
        if self.paged:
            self.block_size = server.block_size
            self.n_lblk = server.n_lblk
            nb = (scfg.pool_blocks if scfg.pool_blocks is not None
                  else nslots * self.n_lblk)
            self._caches = T.init_paged_caches(
                cfg, nslots, scfg.slots, kv_bits=scfg.kv_bits,
                block_size=self.block_size, pool_blocks=nb)
            self.allocator = BlockAllocator(nb, self.block_size)
            self.registry = (
                PrefixRegistry(self.allocator,
                               capacity=scfg.prefix_capacity)
                if server.prefix_sharing else None)
            self._slot_blocks: list = [None] * nslots  # (private_ids, entry)
            self._prefix_keys: dict[int, list[bytes]] = {}
            self.peak_used_blocks = 0
            # summed over each decode segment's live rows (_count_kv)
            self.kv_blocks_reserved = 0
            self.kv_blocks_written = 0
            self.kv_blocks_table = 0
            # chunked prefill: long cold prompts (and registry hits with a
            # long unique suffix) prefill in block-aligned chunks that
            # interleave with decode segments instead of one monolithic
            # admission wave. A mid-admission row occupies its slot +
            # blocks but is not yet live (remaining == 0); its state lives
            # here until the final chunk lands.
            self.chunk = server.chunk_tokens
            self._chunk_state: dict[int, dict] = {}    # slot -> progress
        else:
            self._caches = T.init_caches(cfg, nslots, scfg.slots,
                                         kv_bits=scfg.kv_bits)
            self.allocator = None
            self.registry = None
        self._tok = jnp.zeros((nslots,), jnp.int32)
        self._pos = jnp.zeros((nslots,), jnp.int32)
        # host bookkeeping
        self.remaining = np.zeros((nslots,), np.int64)   # tokens left to emit
        self.slot_req: list[Optional[int]] = [None] * nslots
        self._slot_crit = np.zeros((nslots,), bool)
        self._slot_level = np.zeros((nslots,), np.int32)
        # speculative decode state (ServingConfig.speculate): per-row token
        # history for the n-gram drafter (−1 pad, last entry = the row's
        # current token — updated at the flush boundary) and the per-class
        # opt-out mask (policy.bind_speculative, bound at admission)
        self.spec = bool(scfg.speculate)
        self.draft_w = int(scfg.draft_k) + 1 if self.spec else 1
        if self.spec:
            self._hist = np.full((nslots, int(scfg.draft_hist)), -1,
                                 np.int32)
            self._slot_spec = np.ones((nslots,), bool)
            # (pid, delivered) per verify window, in billing order — the
            # flush-side twin of `events` (which records the PLANNED
            # clamped bills the provisional plan fed select()); together
            # they replay the spec ledger exactly (invariant 11)
            self.spec_billed: list[tuple[int, int]] = []
        self._reqs: dict[int, Request] = {}
        self._suspended: dict[int, RowSnapshot] = {}     # rid -> snapshot
        self.results: dict[int, dict] = {}
        self._n = 0
        self.preemptions = 0
        self.resumes = 0
        self.admission_log: list[int] = []               # rids, admission order
        self.events: list[tuple[int, int, bool]] = []    # (pid, n_rows, crit)
        self._done: list[int] = []                       # completions, in order
        self._inflight: list[dict] = []                  # dispatched, unsynced
        # robustness state: deadlines / cancellation / quarantine / shedding
        self.clock = clock if clock is not None else time.monotonic
        self.shed = shed
        self.faults = faults
        self.retry_budget = int(retry_budget)
        self.watchdog = (Watchdog(float(watchdog_s))
                         if watchdog_s is not None else None)
        self.paranoid = bool(paranoid)
        self._deadline: dict[int, float] = {}     # rid -> absolute deadline
        self._to_reap: dict[int, RequestStatus] = {}     # slot -> status
        self._nf_rows: list[int] = []             # rids w/ non-finite logits
        self._quarantine_q: list[tuple[int, int]] = []   # (ready_round, rid)
        self._attempts: dict[int, int] = {}       # rid -> quarantine retries
        self._q_t0: dict[int, float] = {}         # rid -> first-fault time
        self._round = 0
        self._seg_dt: Optional[float] = None      # step wall-time EMA
        self._flush_idx = 0
        # durability layer (serving/durability.py): when attached, the
        # scheduler notifies it at every lifecycle edge (submit / cancel /
        # finalize / deliver, fsync'd write-ahead records) and flush
        # boundary (checkpoint cadence + crash-point markers). None = the
        # classic in-memory scheduler, zero overhead.
        self.durable = None
        self.draining = False     # graceful drain: stop admitting, finish
        self.cancelled = self.expired = self.shed_count = self.failed = 0
        self.recovered = self.faults_detected = 0
        self.alloc_injected_rounds = 0
        self.recovery_latency: list[float] = []   # seconds, fault -> done
        # the jitted segment/admit executables live on the server, so
        # schedulers can be torn down and rebuilt without recompiling
        self._segment = server._segment
        self._admit = server._admit
        self._admit_paged = server._admit_paged
        self._admit_shared = server._admit_shared
        self._admit_restore = server._admit_restore
        self._clear = server._clear_rows

    # ------------------------------------------------------------- paged util
    def _blocks_needed(self, prompt_len: int, max_new: int) -> int:
        """Physical blocks a request touches over its whole lifetime:
        prompt positions + every decode write, capped at the row's logical
        table (sliding-window rings reuse their blocks by design)."""
        return min(self.n_lblk,
                   -(-(prompt_len + max_new) // self.block_size))

    def _release_blocks(self, blocks) -> None:
        """Return a row's private blocks: ones a registered prefix still
        covers park in the allocator's retired-block LRU (resurrectable by
        a later hash-matched admission, reclaimable under real pressure);
        the rest go straight to the free list."""
        self.allocator.release(
            blocks, cache=(self.registry.covered(blocks)
                           if self.registry is not None else ()))

    def _count_kv(self, slot: int, rid: int) -> None:
        """Count one live row of a decode segment that has run: the blocks
        it holds (private plus mapped shared) into ``kv_blocks_reserved``,
        the blocks its context fills (``prompt + max_new − remaining``
        positions, at most what it holds) into ``kv_blocks_written``, and
        its block table's length into ``kv_blocks_table``."""
        blocks, reg = self._slot_blocks[slot]
        held = len(blocks)
        if reg is not None and reg.block_ids is not None:
            held += reg.n_tokens // self.block_size
        req = self._reqs[rid]
        ctx = len(req.tokens) + req.max_new - int(self.remaining[slot])
        self.kv_blocks_reserved += held
        self.kv_blocks_written += min(held, -(-ctx // self.block_size))
        self.kv_blocks_table += self.n_lblk

    def paged_stats(self) -> dict:
        """Block-pool occupancy + prefix-registry counters (bench JSON).

        Occupancy is **refcount-accurate** and three-way: ``live_blocks``
        (at least one live-row reference, derived from the allocator's
        refcounts — ``used_blocks`` is its alias), ``lru_cached_blocks``
        (retired blocks whose content a registered prefix still wants:
        allocatable capacity AND resurrectable cache, the retired-block
        LRU), and ``free_blocks`` (neither). The three always partition
        the pool — the bench asserts it as a cross-check between the
        refcount, LRU, and free-list bookkeeping. ``kv_blocks_reserved``,
        ``kv_blocks_written`` and ``kv_blocks_table`` are the monotone KV
        counters of :meth:`_count_kv`.
        """
        if not self.paged:
            return {"paged": False,
                    "kv_bytes": T.cache_bytes(self._caches)}
        live = self.allocator.used_blocks
        out = {
            "paged": True,
            "block_size": self.block_size,
            "pool_blocks": self.allocator.n_blocks,
            "used_blocks": live,
            "live_blocks": live,
            "lru_cached_blocks": self.allocator.lru_blocks,
            "reclaimed_blocks": self.allocator.reclaimed_blocks,
            "peak_used_blocks": self.peak_used_blocks,
            "free_blocks": self.allocator.free_blocks,
            "preemptions": self.preemptions,
            "resumes": self.resumes,
            "kv_blocks_reserved": self.kv_blocks_reserved,
            "kv_blocks_written": self.kv_blocks_written,
            "kv_blocks_table": self.kv_blocks_table,
            "kv_bytes": T.cache_bytes(self._caches),
            "registry_bytes": 0,
        }
        if self.registry is not None:
            out.update(registry_entries=len(self.registry),
                       registry_hits=self.registry.hits,
                       registry_misses=self.registry.misses,
                       registry_invalidated=self.registry.invalidated,
                       registry_bytes=self.registry.nbytes())
        return out

    # ------------------------------------------------------------------ queue
    @partial(annotate_function, name="serve.submit")
    def submit(self, request: Request) -> int:
        """Enqueue a request with the scheduling policy. Returns its id.

        Paged pools validate the request up front: one that could never fit
        (more blocks than the whole pool provisions, or — when prefix
        sharing is active — ``prompt + max_new ≥`` the virtual row length,
        which would let its post-retirement ring position wrap onto a
        potentially shared block) raises ``ValueError`` here, cleanly,
        rather than corrupting live rows later. Transient fullness is *not*
        an error: the request queues and admission backpressure (or
        preemption, under a preemptive policy) holds it until blocks free
        up.
        """
        if self.paged and request.max_new > 0:
            plen = len(request.tokens)
            cfg = self.srv.cfg
            if not cfg.sliding_window and self.registry is not None \
                    and plen + request.max_new >= self.srv.slots_p:
                raise ValueError(
                    f"request needs {plen + request.max_new} KV slots but a "
                    f"prefix-sharing paged pool caps rows at "
                    f"{self.srv.slots_p - 1} (slots={self.srv.scfg.slots})")
            need = self._blocks_needed(plen, request.max_new)
            if need > self.allocator.n_blocks:
                raise ValueError(
                    f"request needs {need} KV blocks but the pool has only "
                    f"{self.allocator.n_blocks} "
                    f"(block_size={self.block_size})")
        rid = self._n
        self._n += 1
        self._reqs[rid] = request
        if self.durable is not None:
            # write-ahead: the submit record is durable BEFORE the request
            # can observably exist (invariant 12 — an accepted request is
            # never silently lost by a crash)
            self.durable.on_submit(rid, request)
        if request.deadline_ms is not None:
            self._deadline[rid] = self.clock() + request.deadline_ms / 1e3
        if request.max_new <= 0:        # nothing to generate: done on arrival
            self.results[rid] = {"tokens": [], "profile_trace": [],
                                 "status": RequestStatus.COMPLETED}
            self._done.append(rid)
            return rid
        if self.shed is not None and self.shed.triggered(
                len(self.policy) + 1, self._predicted_misses()):
            # graceful overload degradation: refuse ONE request with a
            # structured SHED status instead of admitting doomed work. The
            # victim is the least urgent party — the queue's class tail if
            # it is strictly less urgent than this arrival, else the
            # arrival itself (so a saver flood can never displace queued
            # critical work, and a critical arrival always lands).
            tail = self.policy.shed_tail()
            if tail is not None and tail[1] > self.policy.klass(
                    request).level:
                vrid = tail[0]
                self.policy.remove(vrid)
                self._suspended.pop(vrid, None)
                self._finalize(vrid, RequestStatus.SHED,
                               reason="overload: displaced by a more "
                                      "urgent arrival")
            else:
                self._finalize(rid, RequestStatus.SHED,
                               reason="overload: queue depth or deadline "
                                      "pressure over threshold")
                return rid
        if self.paged and self.registry is not None:
            # hash block-aligned prefixes once, at enqueue; admission just
            # dictionary-matches them against the registry
            self._prefix_keys[rid] = prefix_keys(
                np.asarray(request.tokens, np.int32), self.block_size)
        self.policy.enqueue(rid, request)
        return rid

    @property
    def live_rows(self) -> int:
        """Pool rows still generating (``remaining > 0``)."""
        return int((self.remaining > 0).sum())

    @property
    def pending(self) -> int:
        """Requests queued but not yet admitted (policy-queue depth;
        suspended rows waiting to resume count — they hold no slot)."""
        return len(self.policy)

    def poll_completed(self) -> list[tuple[int, dict]]:
        """``(rid, result)`` pairs finished since the last poll (completion
        order). Ownership of each result transfers to the caller: the
        scheduler evicts the request's retained state, so a long-lived
        polling server stays O(pool), not O(requests ever served)."""
        done, self._done = self._done, []
        if done and self.durable is not None:
            # deliver record BEFORE handing results out: after a crash,
            # recovery drops exactly the rids the caller already owns
            # (exactly-once delivery), and re-delivers the rest
            self.durable.on_deliver(done)
        out = []
        for rid in done:
            out.append((rid, self.results.pop(rid)))
            self._reqs.pop(rid, None)
            self._deadline.pop(rid, None)
            self._attempts.pop(rid, None)
            self._q_t0.pop(rid, None)
            if self.paged and self.registry is not None:
                self._prefix_keys.pop(rid, None)
        return out

    # ------------------------------------------- request lifecycle (terminal)
    def _finalize(self, rid: int, status: RequestStatus,
                  reason: Optional[str] = None) -> None:
        """Retire a request through its one terminal status: stamp the
        result dict, count it, and queue it for :meth:`poll_completed`.
        Tokens already materialized stay on the result — a cancelled or
        expired request keeps (and was billed for) exactly what it
        actually generated."""
        res = self.results.setdefault(rid,
                                      {"tokens": [], "profile_trace": []})
        res["status"] = status
        if reason is not None:
            res["reason"] = reason
        if rid in self._attempts:
            res["retries"] = self._attempts[rid]
        self._done.append(rid)
        if status is RequestStatus.CANCELLED:
            self.cancelled += 1
        elif status is RequestStatus.EXPIRED:
            self.expired += 1
        elif status is RequestStatus.SHED:
            self.shed_count += 1
        elif status is RequestStatus.FAILED:
            self.failed += 1
        if self.durable is not None:
            self.durable.on_final(rid)

    def cancel(self, rid: int) -> bool:
        """Cancel a request wherever it currently sits; True if it took.

        Queued (including suspended and quarantine-backoff) requests drop
        immediately with ``CANCELLED``. A live pool row (or mid-admission
        chunked row) is *marked*: it is reaped at the next flush boundary
        — every dispatched token materializes first, so the energy ledger
        bills exactly the tokens the request actually generated, and its
        blocks release through the same machinery as :meth:`evict_row`
        (registry entries survive, refcounts stay exact). Returns False
        for unknown rids and for requests already terminal — a request
        whose last tokens are already in flight completes as
        ``COMPLETED``, never half-cancelled.
        """
        took = self._cancel(rid)
        if took and self.durable is not None:
            self.durable.on_cancel(rid)
        return took

    def _cancel(self, rid: int) -> bool:
        if rid not in self._reqs or "status" in self.results.get(rid, {}):
            return False
        if self.policy.remove(rid):
            self._suspended.pop(rid, None)
            self._finalize(rid, RequestStatus.CANCELLED)
            return True
        for i, (_rdy, qrid) in enumerate(self._quarantine_q):
            if qrid == rid:
                del self._quarantine_q[i]
                self._finalize(rid, RequestStatus.CANCELLED)
                return True
        for slot in range(self.n_slots):
            if self.slot_req[slot] == rid:
                if slot in self._to_reap:
                    return False             # already marked for the reaper
                self._to_reap[slot] = RequestStatus.CANCELLED
                return True
        if self.paged:
            for slot, st in self._chunk_state.items():
                if st["rid"] == rid:
                    if slot in self._to_reap:
                        return False
                    self._to_reap[slot] = RequestStatus.CANCELLED
                    return True
        if rid in self._nf_rows:
            # flagged non-finite and its slot already retired: quarantine
            # owns it — cancellation preempts the retry
            self._nf_rows.remove(rid)
            self._finalize(rid, RequestStatus.CANCELLED)
            return True
        return False

    def _eta_s(self, rid: int) -> float:
        """Predicted seconds to finish ``rid`` if admitted now: remaining
        tokens at the observed per-step wall-time EMA (0.0 until a first
        segment calibrates the EMA — admission never rejects blind)."""
        if self._seg_dt is None:
            return 0.0
        req = self._reqs[rid]
        left = req.max_new - len(self.results.get(rid, {}).get("tokens", ()))
        return -(-left // self.quantum) * self._seg_dt

    def _deadline_unreachable(self, rid: int) -> bool:
        dl = self._deadline.get(rid)
        return dl is not None and self.clock() + self._eta_s(rid) > dl

    def _predicted_misses(self) -> int:
        """Queued requests already predicted to miss their deadlines at
        current pool pressure (the ShedPolicy's second trigger)."""
        if self._seg_dt is None or not self._deadline:
            return 0
        return sum(1 for rid in self.policy.rids()
                   if self._deadline_unreachable(rid))

    def _expire(self) -> None:
        """Retire every request whose absolute deadline has passed:
        queued/suspended/backoff requests finalize ``EXPIRED`` now; live
        and chunked rows are marked for the flush-boundary reap (their
        generated-so-far tokens are delivered with the EXPIRED result)."""
        if not self._deadline:
            return
        now = self.clock()
        for rid in self.policy.rids():
            dl = self._deadline.get(rid)
            if dl is not None and now > dl:
                self.policy.remove(rid)
                self._suspended.pop(rid, None)
                self._finalize(rid, RequestStatus.EXPIRED)
        if self._quarantine_q:
            keep = []
            for rdy, rid in self._quarantine_q:
                dl = self._deadline.get(rid)
                if dl is not None and now > dl:
                    self._finalize(rid, RequestStatus.EXPIRED)
                else:
                    keep.append((rdy, rid))
            self._quarantine_q = keep
        for slot in range(self.n_slots):
            if slot in self._to_reap:
                continue
            rid = self.slot_req[slot]
            if rid is None and self.paged and slot in self._chunk_state:
                rid = self._chunk_state[slot]["rid"]
            if rid is None:
                continue
            dl = self._deadline.get(rid)
            if dl is not None and now > dl:
                self._to_reap[slot] = RequestStatus.EXPIRED

    def _reap_marked(self) -> None:
        """Flush-boundary reap of cancelled/expired rows: materialize every
        dispatched token first (billed == delivered, exactly), then release
        each marked row's blocks and unmap its table in one batched clear —
        the same release machinery as :meth:`evict_row`, minus the snapshot
        (nothing resumes)."""
        if not self._to_reap:
            return
        self._flush(0)
        marked, self._to_reap = self._to_reap, {}
        clear = []
        for slot, status in marked.items():
            if self.paged and slot in self._chunk_state:
                st = self._chunk_state.pop(slot)
                rid = st["rid"]
                self._release_blocks(st["blocks"])
                if st["entry"] is not None:
                    self.registry.release(st["entry"])
                clear.append(slot)
                if rid in self._nf_rows:
                    self._nf_rows.remove(rid)
                self._finalize(rid, status)
                continue
            rid = self.slot_req[slot]
            if rid is None:
                continue         # completed inside the in-flight segment
            if rid in self._nf_rows:
                self._nf_rows.remove(rid)    # cancel/expiry beats quarantine
            if self.paged:
                blocks, reg = self._slot_blocks[slot]
                self._release_blocks(blocks)
                if reg is not None:
                    self.registry.release(reg)
                self._slot_blocks[slot] = None
                clear.append(slot)
            self.slot_req[slot] = None
            self._slot_crit[slot] = False
            self._slot_level[slot] = 0
            self.remaining[slot] = 0
            self._finalize(rid, status)
        if self.paged and clear:
            self._caches = self._clear(self._pad_slot_idx(clear),
                                       self._caches)

    def _process_quarantine(self) -> None:
        """Quarantine + precision-fallback retry for rows the decode scan
        flagged non-finite.

        The poisoned row's blocks release through the same machinery as
        :meth:`evict_row`, but no snapshot is taken and the attempt's
        tokens are **discarded**: everything argmaxed after the bad logits
        is garbage, so a retry must restart from the prompt — that is what
        makes the recovered output token-identical to a clean run at the
        escalated profile. Escalation is one rung toward the accuracy
        target: the retry binds ``accuracy_critical=True``, pinning the
        ProfileManager to the highest-accuracy regime (the deterministic,
        ledger-independent selection the oracle tests rely on). The retry
        re-queues at its class front after an exponential backoff
        (1, 2, 4, ... rounds); past ``retry_budget`` attempts the request
        finalizes ``FAILED`` — never a hang, never a corrupted pool."""
        if not self._nf_rows:
            return
        self._flush(0)           # may flag more rows; drain what's known
        rows, self._nf_rows = self._nf_rows, []
        clear = []
        for rid in rows:
            slot = next((s for s in range(self.n_slots)
                         if self.slot_req[s] == rid), None)
            if slot is not None:
                if self.paged:
                    blocks, reg = self._slot_blocks[slot]
                    self._release_blocks(blocks)
                    if reg is not None:
                        self.registry.release(reg)
                    self._slot_blocks[slot] = None
                    clear.append(slot)
                self.slot_req[slot] = None
                self._slot_crit[slot] = False
                self._slot_level[slot] = 0
                self.remaining[slot] = 0
            self.faults_detected += 1
            attempt = self._attempts.get(rid, 0) + 1
            self._attempts[rid] = attempt
            self._q_t0.setdefault(rid, self.clock())
            self.results[rid] = {"tokens": [], "profile_trace": []}
            if attempt > self.retry_budget:
                self._q_t0.pop(rid, None)
                self._finalize(rid, RequestStatus.FAILED,
                               reason="retry budget exhausted")
                continue
            req = self._reqs[rid]
            if not req.accuracy_critical:
                self._reqs[rid] = dataclasses.replace(
                    req, accuracy_critical=True)
            self._quarantine_q.append(
                (self._round + (1 << (attempt - 1)), rid))
        if self.paged and clear:
            self._caches = self._clear(self._pad_slot_idx(clear),
                                       self._caches)

    def check(self) -> None:
        """Full paged-pool invariant audit (no-op on non-paged pools).

        Rebuilds the expected per-block refcounts from first principles —
        one reference per live row's private block, per mid-admission
        chunked row's private block, and per registry sharer of each
        entry block — and hands them to
        :meth:`~repro.serving.paged.BlockAllocator.check`, which also
        verifies the free/LRU/live partition. Raises ``RuntimeError`` on
        any divergence. Cheap (O(pool) host work): the ``paranoid``
        constructor flag runs it after every step.
        """
        if not self.paged:
            return
        exp = np.zeros((self.allocator.n_blocks,), np.int64)
        for slot in range(self.n_slots):
            sb = self._slot_blocks[slot]
            if sb is not None:
                for b in sb[0]:
                    exp[int(b)] += 1
        for st in self._chunk_state.values():
            for b in st["blocks"]:
                exp[int(b)] += 1
        if self.registry is not None:
            self.registry.add_expected_refs(exp)
        self.allocator.check(expected=exp)

    def robustness_stats(self) -> dict:
        """Fault-tolerance counters (bench JSON / ops surface)."""
        out = {"cancelled": self.cancelled, "expired": self.expired,
               "shed": self.shed_count, "failed": self.failed,
               "recovered": self.recovered,
               "faults_detected": self.faults_detected,
               "alloc_injected_rounds": self.alloc_injected_rounds,
               "recovery_latency_s": list(self.recovery_latency),
               "watchdog_stalls": (self.watchdog.stalls
                                   if self.watchdog is not None else 0)}
        if self.faults is not None:
            out.update(injected_nan=self.faults.injected_nan,
                       injected_alloc=self.faults.injected_alloc,
                       injected_stall=self.faults.injected_stall)
        return out

    # -------------------------------------------------------------- admission
    @partial(annotate_function, name="serve.admit")
    def admit(self) -> int:
        """Fill free slots from the policy queue; returns #requests admitted.

        One admission *wave* is ONE device dispatch: every admitted request
        rides in a single ragged prefill (left-padded to a shared pow2 prompt
        bucket, ``prompt_len`` as data — one executable per bucket), first
        tokens come from an on-device argmax, and each prefilled row is
        scattered into its free pool slot, all inside the server's donated
        admit jit. The wave's prefills are billed like the stepwise engine
        bills prefill: one inference per admitted request, under the
        policy-bound profile (an accuracy-critical class pins the wave).

        Paged pools add the wave taxonomy: admission is gated on *blocks*
        as well as slots, candidates are taken strictly in policy order,
        and each round dispatches at most two prefill waves — see
        :meth:`_admit_paged_waves`.

        Admission is deadline-aware: a candidate whose deadline the
        step-time EMA already rules unreachable is rejected here with a
        structured ``EXPIRED`` status instead of admitted as doomed work.
        A :class:`~repro.serving.faults.FaultSchedule` may also declare
        the allocator dry for this round — the round skips entirely, the
        same observable backpressure as a genuinely exhausted pool.
        """
        if self.draining:
            return 0                 # graceful drain: no new admissions
        if self.faults is not None and self.faults.alloc_dry(self._round):
            self.alloc_injected_rounds += 1
            return 0
        if self.paged:
            return self._admit_paged_waves()
        free = [s for s in range(self.n_slots) if self.slot_req[s] is None]
        if not free or not len(self.policy):
            return 0
        rids = []
        while len(rids) < len(free) and len(self.policy):
            rid = self.policy.head()
            if self._deadline_unreachable(rid):
                self.policy.pop_head()
                self._finalize(rid, RequestStatus.EXPIRED,
                               reason="deadline unreachable at admission")
                continue
            rids.append(self.policy.pop_head())
        take = len(rids)
        if not take:
            return 0
        slots = free[:take]
        reqs = [self._reqs[r] for r in rids]
        bucket = _next_pow2(max(self.bucket_min,
                                max(len(r.tokens) for r in reqs)))
        a = _next_pow2(take)               # pow2 wave shape (pad rows drop):
        # a 1–2 row refill costs a 2-row prefill, not a full-pool one, and
        # the executable count stays log² (row bucket × length bucket)
        prompts = np.zeros((a, bucket), np.int32)
        plen = np.zeros((a,), np.int32)    # pad rows: prompt_len 0 → masked
        sidx = np.full((a,), self.n_slots, np.int32)     # OOB → scatter-drop
        for j, r in enumerate(reqs):
            t = np.asarray(r.tokens, np.int32)
            prompts[j, bucket - len(t):] = t             # left-pad
            plen[j] = len(t)
            sidx[j] = slots[j]
        pid = self._bill(reqs)
        tok0, self._tok, self._pos, self._caches = self._admit(
            pid,
            {"tokens": jnp.asarray(prompts),
             "prompt_len": jnp.asarray(plen)},
            jnp.asarray(sidx), self._tok, self._pos, self._caches)
        entry = {"kind": "admit", "toks": tok0,
                 "name": self.srv.engine.profile_names[pid],
                 "rows": [], "completes": []}
        for j, (rid, slot) in enumerate(zip(rids, slots)):
            req = self._reqs[rid]
            self.results[rid] = {"tokens": [], "profile_trace": []}
            entry["rows"].append((j, rid))
            if self.record_events:
                self.admission_log.append(rid)
            if req.max_new == 1:                         # already complete
                entry["completes"].append(rid)
                continue
            self.slot_req[slot] = rid
            self._slot_crit[slot] = self.policy.bind_critical(req)
            self._slot_level[slot] = self.policy.klass(req).level
            self.remaining[slot] = req.max_new - 1
            self._seed_spec(slot, req)
        self._inflight.append(entry)
        return take

    def _admit_paged_waves(self) -> int:
        """Policy-ordered claim of slots *and* blocks, then ≤2 prefill
        dispatches per round.

        Candidates classify by the wave *kind* they need — **cold** (full
        ragged prefill; long prompts chunk their first chunk in), **shared**
        (registry hits and intra-wave-dedup deferrals: suffix-only
        continuation prefill; hits with a long unique suffix chunk too),
        or **resume** (suspended rows replaying their snapshot through the
        restore executable, grouped by their pinned profile). A round
        commits to at most TWO kinds: a head candidate needing a third
        waits for the next round — that cap, plus rolling
        deferred-registration failures back to the class head instead of
        dispatching a fallback wave, is the ≤2-dispatches-per-admission-
        round invariant the policy tests guard. Before any classification,
        a preemptive policy gets the chance to evict victims for an urgent
        head that would otherwise not fit (:meth:`_maybe_preempt`).

        Intra-wave prefix dedup survives the refactor: a cold candidate
        whose prefix will be registered by an earlier candidate of THIS
        round's cold wave is *deferred* — it resolves against the registry
        right after the cold wave dispatches (and registers), then rides
        the shared wave, so two identical prompts arriving in the same
        cold wave no longer both prefill the prefix. Rollbacks keep their
        relative order; the strict stop-at-first-failure contract
        otherwise holds within each class. Chunk *continuation* waves —
        :meth:`_advance_chunks`, one per in-flight pinned profile per
        round — count against the same two-dispatch budget: a round that
        will advance chunks admits at most ``2 - groups`` new kinds, so
        the audited ceiling holds even when restart recovery floods one
        round with resumable rows, queued candidates AND restored
        mid-prompt chunks at once.
        """
        self._maybe_preempt()
        free = [s for s in range(self.n_slots)
                if self.slot_req[s] is None and s not in self._chunk_state]
        cold, shared, deferred, chunked = [], [], [], []
        shared_chunked, resume = [], []
        resume_pid: Optional[int] = None
        kinds: set = set()
        # imminent chunk-continuation dispatches (one per pinned profile,
        # rows admitted THIS round are "fresh" and sit out) pre-commit part
        # of the round's two-dispatch budget
        kind_cap = 2 - len({st["pid"] for st in self._chunk_state.values()
                            if not st.get("fresh")})
        pending: dict[bytes, int] = {}   # key -> n_tokens this wave registers
        while free and len(self.policy):
            rid = self.policy.head()
            if self._deadline_unreachable(rid):
                self.policy.pop_head()
                self._suspended.pop(rid, None)
                self._finalize(rid, RequestStatus.EXPIRED,
                               reason="deadline unreachable at admission")
                continue
            req = self._reqs[rid]
            if rid in self._suspended:
                if "resume" not in kinds and len(kinds) >= kind_cap:
                    break                # over-budget wave kind waits a round
                snap = self._suspended[rid]
                if resume and snap.pid != resume_pid:
                    break                # one pinned-pid resume group/round
                blocks = self.allocator.alloc(
                    self._blocks_needed(len(req.tokens), req.max_new))
                if blocks is None:
                    break                # backpressure: head waits
                self.policy.pop_head()
                resume.append((rid, free.pop(0), blocks))
                resume_pid = snap.pid
                kinds.add("resume")
                continue
            plen = len(req.tokens)
            need = self._blocks_needed(plen, req.max_new)
            # a quarantine retry must NOT hit the prefix registry: a match
            # would map prompt blocks prefilled under the faulted attempt's
            # (or any other wave's) profile, and the recovered output would
            # no longer be token-identical to a clean run at the escalated
            # profile — the retry recomputes its whole prompt, cold
            keys = (self._prefix_keys.get(rid, [])
                    if self._attempts.get(rid, 0) == 0 else [])
            entry, wait, n_shared = None, False, 0
            if self.registry is not None:
                entry = self.registry.lookup(keys)
            if entry is not None:
                self.registry.acquire(entry)     # references (or resurrects
                if entry.block_ids is not None:  # from the LRU) its blocks
                    n_shared = entry.n_tokens // self.block_size
            elif pending:
                for k in keys:                   # longest-first, like lookup
                    if k in pending:
                        wait = True
                        if self.srv.scfg.kv_bits == 16:
                            n_shared = pending[k] // self.block_size
                        break
            kind = "shared" if (entry is not None or wait) else "cold"
            if kind not in kinds and len(kinds) >= kind_cap:
                if entry is not None:
                    self.registry.release(entry)
                break                            # over budget: next round
            blocks = self.allocator.alloc(need - n_shared)
            if blocks is None:                   # backpressure: head waits,
                if entry is not None:            # policy order preserved
                    self.registry.release(entry)
                break
            self.policy.pop_head()
            slot = free.pop(0)
            kinds.add(kind)
            if self.registry is not None and not wait:
                self.registry.record_admission(entry)
            if entry is not None:
                if self.chunk and plen - entry.n_tokens > self.chunk:
                    shared_chunked.append((rid, slot, entry, blocks))
                else:
                    shared.append((rid, slot, entry, blocks))
            elif wait:
                deferred.append((rid, slot, blocks, keys))
            elif self.chunk and plen > self.chunk:
                chunked.append((rid, slot, blocks))
            else:
                cold.append((rid, slot, blocks))
                if self.registry is not None:
                    j_max = (plen - 1) // self.block_size
                    for i, k in enumerate(keys):     # chain, longest first
                        pending.setdefault(
                            k, (j_max - i) * self.block_size)
        n = 0
        if cold or chunked:
            n += self._dispatch_cold(cold, chunked)
        rollback: list[int] = []
        for rid, slot, blocks, keys in deferred:
            # the cold wave above has dispatched and registered its chains;
            # a deferred candidate now hits the registry like any other.
            # The entry actually registered may cover a different prefix
            # length than the deferral assumed (LRU capacity), so square up
            # the private-block allocation before dispatching. If the
            # registration (or the top-up) failed, the candidate rolls back
            # to its class head for the next round — no fallback wave, the
            # ≤2-dispatch round contract holds.
            req = self._reqs[rid]
            need = self._blocks_needed(len(req.tokens), req.max_new)
            entry = self.registry.lookup(keys)
            if entry is None:
                self.allocator.release(blocks)
                rollback.append(rid)
                continue
            self.registry.acquire(entry)
            n_shared = (entry.n_tokens // self.block_size
                        if entry.block_ids is not None else 0)
            n_priv = need - n_shared
            if len(blocks) > n_priv:
                self.allocator.release(blocks[n_priv:])
                blocks = blocks[:n_priv]
            elif len(blocks) < n_priv:
                extra = self.allocator.alloc(n_priv - len(blocks))
                if extra is None:
                    self.registry.release(entry)
                    self.allocator.release(blocks)
                    rollback.append(rid)
                    continue
                blocks = blocks + extra
            self.registry.record_admission(entry)
            if self.chunk and len(req.tokens) - entry.n_tokens > self.chunk:
                shared_chunked.append((rid, slot, entry, blocks))
            else:
                shared.append((rid, slot, entry, blocks))
        if shared or shared_chunked:
            n += self._dispatch_shared(shared, shared_chunked)
        if resume:
            n += self._dispatch_resume(resume)
        for rid in reversed(rollback):      # preserve their relative order
            self.policy.push_front(rid, self._reqs[rid])
        if n:
            self.peak_used_blocks = max(self.peak_used_blocks,
                                        self.allocator.used_blocks)
        self._advance_chunks()
        return n

    def _bill(self, reqs) -> int:
        """Select/account the wave's profile (one inference per request).
        The policy resolves the wave's accuracy binding: any row of an
        accuracy-critical class (or with its own critical flag) pins the
        selection to the accuracy target."""
        mgr = self.srv.manager
        crit = self.policy.wave_critical(reqs)
        pid = 0 if mgr is None else mgr.select(crit)
        if mgr is not None:
            mgr.account(pid, len(reqs))
        if self.record_events:
            self.events.append((pid, len(reqs), crit))
        return pid

    def _pad_slot_idx(self, slots: list) -> jnp.ndarray:
        """Fixed-shape ``[n_slots]`` slot-index vector (OOB-padded) so row
        clearing reuses one executable regardless of how many rows retire."""
        out = np.full((self.n_slots,), self.n_slots, np.int32)
        out[:len(slots)] = slots
        return jnp.asarray(out)

    # ------------------------------------------------------------- preemption
    def _maybe_preempt(self) -> None:
        """Preemption trigger: the policy-queue head belongs to a class that
        may preempt, and the pool cannot take it — no free slot, or the
        allocator (free + reclaimable LRU) cannot cover its blocks. The
        policy picks victims (default: lowest class first, fewest generated
        tokens first, all-or-nothing); each is suspended via
        :meth:`evict_row` and all victim tables unmap in ONE fixed-shape
        clear dispatch. Victim private-block counts are what eviction
        actually frees (shared CoW blocks only drop references)."""
        if not self.policy.preemptive or self._admit_restore is None:
            return
        rid = self.policy.head()
        if rid is None:
            return
        req = self._reqs[rid]
        need = self._blocks_needed(len(req.tokens), req.max_new)
        if rid not in self._suspended and self.registry is not None:
            # a registry hit maps its prefix blocks instead of allocating
            # them — count only the private need, or a hit-holding critical
            # arrival would evict savers the classification loop was never
            # going to need evicted (lookup is a pure read: no LRU churn)
            entry = self.registry.lookup(self._prefix_keys.get(rid, []))
            if entry is not None and entry.block_ids is not None:
                need -= entry.n_tokens // self.block_size
        have_slot = any(self.slot_req[s] is None
                        and s not in self._chunk_state
                        for s in range(self.n_slots))
        need_slots = 0 if have_slot else 1
        need_blocks = max(0, need - self.allocator.available_blocks)
        if not need_slots and not need_blocks:
            return
        rows = []
        for slot in range(self.n_slots):
            vrid = self.slot_req[slot]
            if vrid is None or slot in self._chunk_state:
                continue
            vreq = self._reqs[vrid]
            blocks, _reg = self._slot_blocks[slot]
            rows.append(RowState(
                slot=slot, rid=vrid, level=int(self._slot_level[slot]),
                generated=len(self.results[vrid]["tokens"]),
                blocks=len(blocks),
                preemptible=self.policy.klass(vreq).preemptible))
        victims = self.policy.pick_victims(req, rows, need_slots,
                                           need_blocks)
        if not victims:
            return
        for v in victims:
            self.evict_row(v.slot)
        self._caches = self._clear(
            self._pad_slot_idx([v.slot for v in victims]), self._caches)

    def _snapshot_row(self, slot: int) -> RowSnapshot:
        """Materialize a live row's :class:`RowSnapshot` — the row's true
        progress as replayable data (f32 masters + exact int-KV scale
        preimages). Pure read; the caller must have flushed every
        in-flight token first (``_flush(0)``) so the snapshot reflects the
        row's real position. Shared by the preemption SUSPEND edge
        (:meth:`evict_row`) and the durability layer's live-state
        checkpoint — crash recovery replays the exact same bytes through
        the exact same restore executable."""
        rid = self.slot_req[slot]
        req = self._reqs[rid]
        res = self.results[rid]
        g = len(res["tokens"])              # ≥ 1: admission emitted one
        p_written = len(req.tokens) + g - 1  # KV positions 0..p_written-1
        pid = self.srv.engine.profile_names.index(res["profile_trace"][-1])
        blocks, reg = self._slot_blocks[slot]
        ns = (reg.n_tokens // self.block_size
              if reg is not None and reg.block_ids is not None else 0)
        row_map = ([int(b) for b in reg.block_ids[:ns]] if ns else []) \
            + list(blocks)
        mk, mv = T.paged_row_masters(self._caches["kv"], slot, row_map,
                                     p_written)
        ka = va = ksc = vsc = None
        kv_bits = self.srv.scfg.kv_bits
        if kv_bits in (4, 8):
            qmax = 127.0 if kv_bits == 8 else 7.0
            pool = self._caches["kv"]
            # repro: allow(host-sync) suspend edge materializes masters
            ksc = np.asarray(pool.k_scale[:, slot])
            # repro: allow(host-sync) suspend edge materializes masters
            vsc = np.asarray(pool.v_scale[:, slot])
            # best-effort preimages: XLA's reciprocal-multiply /qmax can
            # emit scales with no exact division preimage (seen at qmax=7);
            # the exact scales ride along and are forced post-restore.
            ka = jnp.asarray(T.amax_for_scale(ksc, qmax, strict=False))
            va = jnp.asarray(T.amax_for_scale(vsc, qmax, strict=False))
            ksc, vsc = jnp.asarray(ksc), jnp.asarray(vsc)
        return RowSnapshot(
            rid=rid, n_done=p_written,
            last_tok=int(res["tokens"][-1]), pid=pid,
            master_k=mk, master_v=mv, k_amax=ka, v_amax=va,
            k_scale=ksc, v_scale=vsc)

    def evict_row(self, slot: int) -> int:
        """Suspend one live pool row; returns its rid.

        The preemption state machine's SUSPEND edge: flush every in-flight
        token (the snapshot needs the row's true progress), snapshot the
        row's block table + host-side KV masters
        (:class:`~repro.serving.paged.RowSnapshot` — masters via
        :func:`repro.models.transformer.paged_row_masters`, exact int-KV
        scale preimages via :func:`~repro.models.transformer.
        amax_for_scale`), release its blocks (registered prefixes park in
        the retired-block LRU; a mapped CoW entry just drops this sharer's
        references), and requeue the request at the front of its class.
        The caller unmaps the slot's block table (``_clear_rows``) — the
        host-side twin of in-graph retirement, so the row's residual
        frozen-position writes can never follow the freed blocks to their
        next owner. The row later resumes through
        :meth:`_dispatch_resume`, token-identically.
        """
        rid = self.slot_req[slot]
        assert rid is not None and slot not in self._chunk_state
        self._flush(0)
        self._suspended[rid] = self._snapshot_row(slot)
        req = self._reqs[rid]
        blocks, reg = self._slot_blocks[slot]
        self._release_blocks(blocks)
        if reg is not None:
            self.registry.release(reg)
        self._slot_blocks[slot] = None
        self.slot_req[slot] = None
        self._slot_crit[slot] = False
        self._slot_level[slot] = 0
        self.remaining[slot] = 0
        self.policy.push_front(rid, req)
        self.preemptions += 1
        return rid

    def _dispatch_resume(self, rows) -> int:
        """One continuation wave re-admitting suspended rows — the RESUME
        edge of the preemption state machine, riding the restore
        executable (the master-replay continuation body; at int KV it IS
        the shared-admission executable).

        The "prefix" is EVERYTHING the row had written when evicted
        (positions ``0..P−1``, replayed from the snapshot masters) and the
        "suffix" is **empty** (``prompt_len = 0`` — every suffix write is
        masked out of the scatter): the wave is pure data movement, so the
        restored cache bytes, scales and ``token_idx`` are identical to
        the suspended row's by construction — at kv16 the masters
        round-trip through bf16, at int KV re-quantization under the
        snapshot's exact scale preimage reproduces every int — never by
        floating-point luck. It recomputes no token and **bills nothing**:
        a request's total billed inferences are invariant under
        preemption. All rows of the wave share the snapshot-pinned
        profile (their last pre-eviction step's — bookkeeping only; no
        profile-dependent compute lands in the cache). After the dispatch
        the decode carry is re-pointed at the recorded last emitted token
        (the empty-suffix wave's argmax is meaningless); with
        ``pos = P`` set by the wave, the carry equals the uninterrupted
        row's exactly, and the next segment continues it bit-for-bit.
        """
        bs = self.block_size
        snaps = [self._suspended.pop(rid) for rid, _, _ in rows]
        pid = snaps[0].pid
        sb = _next_pow2(self.bucket_min)            # empty suffixes
        pp = bs * _next_pow2(max(-(-s.n_done // bs) for s in snaps))
        a = _next_pow2(len(rows))
        nb_oob = self.allocator.n_blocks
        prompts = np.zeros((a, sb), np.int32)
        slen = np.zeros((a,), np.int32)             # 0: nothing prefills
        plen_pre = np.zeros((a,), np.int32)
        sidx = np.full((a,), self.n_slots, np.int32)
        dest = np.full((a, self.n_lblk), nb_oob, np.int32)
        bt_rows = np.full((a, self.n_lblk), nb_oob, np.int32)
        for j, ((rid, slot, blocks), s) in enumerate(zip(rows, snaps)):
            plen_pre[j] = s.n_done
            sidx[j] = slot
            dest[j, :len(blocks)] = blocks          # fully private rebuild
            bt_rows[j, :len(blocks)] = blocks
        batch = {"tokens": jnp.asarray(prompts),
                 "prompt_len": jnp.asarray(slen)}
        self._call_continuation(
            self._admit_restore, pid, batch, sidx, dest, bt_rows, plen_pre,
            pp, [(s.n_done, None, s.master_k, s.master_v, s.k_amax, s.v_amax)
                 for s in snaps], masters=True)
        sl = jnp.asarray(np.asarray([slot for _, slot, _ in rows], np.int32))
        if snaps[0].k_scale is not None:
            # force the suspended rows' exact scales over the wave's
            # recalibration: the amax preimages are best-effort (XLA's
            # /qmax lowering can produce scales with no exact preimage),
            # and while the re-quantized ints are identical either way,
            # the scale bytes themselves must match the uninterrupted
            # row's for the next segment to be bit-exact.
            kv = self._caches["kv"]
            self._caches["kv"] = kv._replace(
                k_scale=kv.k_scale.at[:, sl].set(
                    jnp.stack([s.k_scale for s in snaps], axis=1)),
                v_scale=kv.v_scale.at[:, sl].set(
                    jnp.stack([s.v_scale for s in snaps], axis=1)))
        self._tok = self._tok.at[sl].set(
            jnp.asarray(np.asarray([s.last_tok for s in snaps], np.int32)))
        for (rid, slot, blocks), s in zip(rows, snaps):
            req = self._reqs[rid]
            self.slot_req[slot] = rid
            self._slot_crit[slot] = self.policy.bind_critical(req)
            self._slot_level[slot] = self.policy.klass(req).level
            self.remaining[slot] = \
                req.max_new - len(self.results[rid]["tokens"])
            self._slot_blocks[slot] = (blocks, None)
            self._seed_spec(slot, req,
                            history=self.results[rid]["tokens"])
            self.resumes += 1
        return len(rows)

    # ------------------------------------------------------------------ waves
    def _dispatch_cold(self, rows, chunked=()) -> int:
        """One ``_admit_paged`` wave: full ragged prefill + block scatter.

        ``chunked`` rows ride the same wave but prefill only their FIRST
        ``chunk`` tokens; the rest of the prompt follows one chunk per
        admission round through :meth:`_advance_chunks` continuation waves.
        A chunked row holds its slot and blocks from here on but is not yet
        live (``remaining`` stays 0 — the done-mask keeps it frozen through
        the decode segments that run between its chunks).
        """
        allrows = list(rows) + list(chunked)
        n_cold = len(rows)
        reqs = [self._reqs[rid] for rid, _, _ in allrows]
        lens = [len(r.tokens) if j < n_cold else min(len(r.tokens), self.chunk)
                for j, r in enumerate(reqs)]
        bucket = _next_pow2(max(self.bucket_min, max(lens)))
        a = _next_pow2(len(allrows))
        nb_oob = self.allocator.n_blocks
        prompts = np.zeros((a, bucket), np.int32)
        plen = np.zeros((a,), np.int32)
        sidx = np.full((a,), self.n_slots, np.int32)
        dest = np.full((a, self.n_lblk), nb_oob, np.int32)
        for j, (rid, slot, blocks) in enumerate(allrows):
            t = np.asarray(reqs[j].tokens, np.int32)[:lens[j]]
            prompts[j, bucket - lens[j]:] = t                # left-pad
            plen[j] = lens[j]
            sidx[j] = slot
            dest[j, :len(blocks)] = blocks
        pid = self._bill(reqs)
        tok0, raw, self._tok, self._pos, self._caches = self._admit_paged(
            pid,
            {"tokens": jnp.asarray(prompts),
             "prompt_len": jnp.asarray(plen)},
            jnp.asarray(sidx), jnp.asarray(dest),
            self._tok, self._pos, self._caches)
        if self.registry is not None and rows:
            self._register_prefixes(rows, reqs[:n_cold], raw, bucket)
        for off, (rid, slot, blocks) in enumerate(chunked):
            j = n_cold + off
            st = {"rid": rid, "blocks": blocks, "done": lens[j],
                  "map": list(blocks),  # logical→physical incl. shared span
                  "entry": None, "n_shared": 0,
                  "fresh": True,   # chunk 2 waits for the next round — one
                                   # chunk wave per row per admission round
                  "pid": pid,      # profile pinned for the WHOLE prompt:
                                   # a monolithic admission prefills under
                                   # one profile, so chunks must too or the
                                   # row's KV would mix precisions no cold
                                   # path can produce (token identity)
                  "mk": None, "mv": None, "ka": None, "va": None}
            if raw is not None:
                # int KV: keep the chunk's pre-quantization K/V + running
                # amax so the next chunk can replay it as its prefix
                # masters (the exact-scale recalibration path)
                k_all, v_all = raw
                c0 = bucket - lens[j]
                st["mk"] = k_all[:, j, c0:].astype(jnp.float32)
                st["mv"] = v_all[:, j, c0:].astype(jnp.float32)
                st["ka"] = jnp.max(jnp.abs(st["mk"]), axis=(1, 3))
                st["va"] = jnp.max(jnp.abs(st["mv"]), axis=(1, 3))
            self._chunk_state[slot] = st
            self.results[rid] = {"tokens": [], "profile_trace": []}
            if self.record_events:
                self.admission_log.append(rid)
        self._post_admission(tok0, self.srv.engine.profile_names[pid],
                             [(j, rid, slot, blocks, None)
                              for j, (rid, slot, blocks) in enumerate(rows)])
        return len(allrows)

    def _register_prefixes(self, rows, reqs, raw, bucket: int) -> None:
        """Offer each new prompt's block-aligned prefix chain for reuse.

        The chain discipline lives in :meth:`~repro.serving.paged.
        PrefixRegistry.register_chain`; this method only slices each row's
        pre-quantization masters out of the wave (int KV — one lazily
        sliced device array shared by the whole chain; at kv16 the pool's
        bf16 blocks double as the masters and nothing is stored).
        """
        kv16 = self.srv.scfg.kv_bits == 16
        bs = self.block_size
        for j, (rid, slot, blocks) in enumerate(rows):
            t = np.asarray(reqs[j].tokens, np.int32)
            j_max = (len(t) - 1) // bs
            mk = mv = None
            if raw is not None and j_max >= 1:
                k_all, v_all = raw
                c0 = bucket - len(t)
                mk = k_all[:, j, c0:c0 + j_max * bs].astype(jnp.float32)
                mv = v_all[:, j, c0:c0 + j_max * bs].astype(jnp.float32)
            # kv16_masters: blocks stay shareable (the bf16 pool is still
            # exact) AND the f32 masters ride along for durable snapshots
            self.registry.register_chain(self._prefix_keys.get(rid, []),
                                         j_max, blocks, mk, mv,
                                         share_blocks=kv16)

    def _call_continuation(self, fn, pid, batch, sidx, dest, bt_rows,
                           plen_pre, pp: int, pre: list,
                           masters: bool = False):
        """Assemble the prefix operands and dispatch one continuation-
        prefill wave — the single place that knows the executable's calling
        convention, shared by registry-hit admissions
        (:meth:`_dispatch_shared`), chunk continuations
        (:meth:`_dispatch_chunks`) and preemption resumes
        (:meth:`_dispatch_resume`).

        ``pre``: one ``(n_tok, block_ids, mk, mv, ka, va)`` tuple per wave
        row. At kv16 the prefix is normally gathered in-jit from
        ``block_ids`` (the bf16 pool is its own master); ``masters=True``
        forces the master-replay convention regardless of precision — the
        resume path, where the evicted row's blocks are gone and its
        snapshot is the only source. At int KV the full-precision masters
        ``mk``/``mv`` (sliced to ``n_tok`` — chain entries share one
        buffer — and padded to the ``pp`` bucket) are replayed with their
        raw amax. Returns ``(tok0, raw)``.
        """
        cfg = self.srv.cfg
        a = dest.shape[0]
        nb_oob = self.allocator.n_blocks
        if not self.srv.masters_mode and not masters:
            pb = pp // self.block_size
            pre_bids = np.full((a, pb), nb_oob, np.int32)
            for j, (n_tok, bids, *_rest) in enumerate(pre):
                nbl = n_tok // self.block_size
                pre_bids[j, :nbl] = bids[:nbl]
            tok0, raw, self._tok, self._pos, self._caches = fn(
                pid, batch, jnp.asarray(sidx), jnp.asarray(dest),
                jnp.asarray(bt_rows), jnp.asarray(pre_bids),
                jnp.asarray(plen_pre), self._tok, self._pos,
                self._caches)
            return tok0, raw

        def padm(m, n_tok):
            m = m[:, :n_tok].astype(jnp.float32)
            return (m if n_tok == pp else
                    jnp.pad(m, ((0, 0), (0, pp - n_tok), (0, 0), (0, 0))))

        zk = jnp.zeros((cfg.n_layers, pp, cfg.n_kv, cfg.hd), jnp.float32)
        za = jnp.zeros((cfg.n_layers, cfg.n_kv), jnp.float32)
        npad = a - len(pre)
        kpre = jnp.stack([padm(mk, n) for n, _, mk, _, _, _ in pre]
                         + [zk] * npad, axis=1)
        vpre = jnp.stack([padm(mv, n) for n, _, _, mv, _, _ in pre]
                         + [zk] * npad, axis=1)
        ka = jnp.stack([za if ka_ is None else ka_
                        for *_x, ka_, _va in pre] + [za] * npad, axis=1)
        va = jnp.stack([za if va_ is None else va_
                        for *_x, va_ in pre] + [za] * npad, axis=1)
        tok0, raw, self._tok, self._pos, self._caches = fn(
            pid, batch, jnp.asarray(sidx), jnp.asarray(dest),
            jnp.asarray(bt_rows), kpre, vpre, ka, va,
            jnp.asarray(plen_pre), self._tok, self._pos, self._caches)
        return tok0, raw

    def _dispatch_shared(self, rows, chunked=()) -> int:
        """One ``_admit_shared`` wave: suffix-only continuation prefill.

        ``chunked`` rows are registry hits whose unique suffix exceeds the
        prefill chunk: they ride the same wave but prefill only the FIRST
        ``chunk`` suffix tokens, then advance one chunk per admission round
        through :meth:`_advance_chunks` exactly like a long cold prompt —
        the prefix-chain hit just moved their starting line (closes the
        chunk-from-hit gap: before this, a hit with a long unique suffix
        prefilled that suffix monolithically, stalling every live row).
        """
        bs = self.block_size
        allrows = list(rows) + list(chunked)
        n_full = len(rows)
        reqs = [self._reqs[rid] for rid, _, _, _ in allrows]
        sufs = []
        for j, (r, (_, _, e, _)) in enumerate(zip(reqs, allrows)):
            s = np.asarray(r.tokens, np.int32)[e.n_tokens:]
            sufs.append(s if j < n_full else s[:self.chunk])
        sb = _next_pow2(max(self.bucket_min, max(len(s) for s in sufs)))
        pp = bs * _next_pow2(max(-(-e.n_tokens // bs)
                                 for _, _, e, _ in allrows))
        a = _next_pow2(len(allrows))
        nb_oob = self.allocator.n_blocks
        prompts = np.zeros((a, sb), np.int32)
        slen = np.zeros((a,), np.int32)
        plen_pre = np.zeros((a,), np.int32)
        sidx = np.full((a,), self.n_slots, np.int32)
        dest = np.full((a, self.n_lblk), nb_oob, np.int32)
        bt_rows = np.full((a, self.n_lblk), nb_oob, np.int32)
        for j, ((rid, slot, e, blocks), suf) in enumerate(zip(allrows, sufs)):
            prompts[j, sb - len(suf):] = suf                 # left-pad
            slen[j] = len(suf)
            plen_pre[j] = e.n_tokens
            sidx[j] = slot
            ns = e.n_tokens // bs if e.block_ids is not None else 0
            if ns:
                bt_rows[j, :ns] = e.block_ids[:ns]           # mapped, shared
            bt_rows[j, ns:ns + len(blocks)] = blocks         # private tail
            dest[j, ns:ns + len(blocks)] = blocks            # only these get
        ents = [e for _, _, e, _ in allrows]                 # written (CoW)
        pid = self._bill(reqs)
        batch = {"tokens": jnp.asarray(prompts),
                 "prompt_len": jnp.asarray(slen)}
        tok0, raw = self._call_continuation(
            self._admit_shared, pid, batch, sidx, dest, bt_rows, plen_pre,
            pp, [(e.n_tokens, e.block_ids, e.master_k, e.master_v,
                  e.k_amax, e.v_amax) for e in ents])
        for off, (rid, slot, e, blocks) in enumerate(chunked):
            j = n_full + off
            ns = e.n_tokens // bs if e.block_ids is not None else 0
            st = {"rid": rid, "blocks": blocks,
                  "map": ([int(b) for b in e.block_ids[:ns]] if ns else [])
                         + list(blocks),
                  "entry": e, "n_shared": ns,
                  "done": e.n_tokens + len(sufs[j]),
                  "fresh": True, "pid": pid,
                  "mk": None, "mv": None, "ka": None, "va": None}
            if raw is not None:
                # int KV: seed the accumulated masters with the ENTRY's
                # prefix masters + this wave's raw suffix, so later chunks
                # replay the full processed span with running-amax scales
                k_all, v_all = raw
                c0 = sb - len(sufs[j])
                new_k = k_all[:, j, c0:].astype(jnp.float32)
                new_v = v_all[:, j, c0:].astype(jnp.float32)
                st["mk"] = jnp.concatenate(
                    [e.master_k[:, :e.n_tokens].astype(jnp.float32), new_k],
                    axis=1)
                st["mv"] = jnp.concatenate(
                    [e.master_v[:, :e.n_tokens].astype(jnp.float32), new_v],
                    axis=1)
                st["ka"] = jnp.maximum(
                    e.k_amax, jnp.max(jnp.abs(new_k), axis=(1, 3)))
                st["va"] = jnp.maximum(
                    e.v_amax, jnp.max(jnp.abs(new_v), axis=(1, 3)))
            self._chunk_state[slot] = st
            self.results[rid] = {"tokens": [], "profile_trace": []}
            if self.record_events:
                self.admission_log.append(rid)
        self._post_admission(tok0, self.srv.engine.profile_names[pid],
                             [(j, rid, slot, blocks, e)
                              for j, (rid, slot, e, blocks)
                              in enumerate(rows)])
        return len(allrows)

    def _advance_chunks(self) -> None:
        """Advance every mid-admission chunked row by one prompt chunk.

        Called once per admission round, BETWEEN decode segments — that
        interleaving is the whole point: a 4-chunk prompt costs four small
        continuation dispatches with decode quanta in between instead of
        one monolithic wave that stalls every live row for the full
        prompt's prefill.
        """
        if not self._chunk_state:
            return
        waves: dict[int, list] = {}          # rows grouped by pinned profile
        for slot in sorted(self._chunk_state):
            st = self._chunk_state[slot]
            if st.pop("fresh", False):       # admitted this round: a decode
                continue                     # segment runs before chunk 2
            t = np.asarray(self._reqs[st["rid"]].tokens, np.int32)
            clen = min(self.chunk, len(t) - st["done"])
            waves.setdefault(st["pid"], []).append(
                (slot, st, t[st["done"]:st["done"] + clen]))
        for pid, rows in waves.items():
            self._dispatch_chunks(pid, rows)

    def _dispatch_chunks(self, pid: int, rows) -> None:
        """One continuation wave over ``(slot, state, chunk_tokens)`` rows,
        all pinned to profile ``pid`` (the one their first chunk billed).

        Reuses the shared-prefix executable verbatim: the "prefix" is the
        row's own previously processed span — gathered from its mapped
        blocks at kv16 (for a chunk-from-hit row that includes the shared
        CoW prefix blocks, read-only; chunk boundaries are block-aligned
        by construction), replayed from the accumulated full-precision
        masters at int KV. ``dest`` rewrites the row's PRIVATE blocks each
        chunk, which both lands the new chunk and scrubs any junk a frozen
        row's residual decode writes parked there between chunks (frozen
        positions are always past the shared span, so the shared blocks
        never need — or get — a write). Rows whose final chunk lands go
        live (``remaining = max_new − 1``) with their first generated
        token coming from this wave's argmax — exactly the cold admission
        contract.
        """
        bs = self.block_size
        sb = _next_pow2(max(self.bucket_min,
                            max(len(c) for _, _, c in rows)))
        pp = bs * _next_pow2(max(st["done"] // bs for _, st, _ in rows))
        a = _next_pow2(len(rows))
        nb_oob = self.allocator.n_blocks
        prompts = np.zeros((a, sb), np.int32)
        slen = np.zeros((a,), np.int32)
        plen_pre = np.zeros((a,), np.int32)
        sidx = np.full((a,), self.n_slots, np.int32)
        dest = np.full((a, self.n_lblk), nb_oob, np.int32)
        bt_rows = np.full((a, self.n_lblk), nb_oob, np.int32)
        for j, (slot, st, chunk) in enumerate(rows):
            prompts[j, sb - len(chunk):] = chunk             # left-pad
            slen[j] = len(chunk)
            plen_pre[j] = st["done"]
            sidx[j] = slot
            ns = st["n_shared"]
            bt_rows[j, :len(st["map"])] = st["map"]
            dest[j, ns:ns + len(st["blocks"])] = st["blocks"]
        # continuation waves reuse the pinned profile and bill nothing new —
        # the request was billed its one prefill inference at the first
        # chunk, and re-selecting here could mix precisions within one
        # prompt's KV (no monolithic admission can produce that state)
        batch = {"tokens": jnp.asarray(prompts),
                 "prompt_len": jnp.asarray(slen)}
        tok0, raw = self._call_continuation(
            self._admit_shared, pid, batch, sidx, dest, bt_rows, plen_pre,
            pp, [(st["done"], st["map"], st["mk"], st["mv"],
                  st["ka"], st["va"]) for _, st, _ in rows])
        entry = {"kind": "admit", "toks": tok0,
                 "name": self.srv.engine.profile_names[pid],
                 "rows": [], "completes": []}
        clear = []
        for j, (slot, st, chunk) in enumerate(rows):
            st["done"] += len(chunk)
            if raw is not None:
                k_all, v_all = raw
                c0 = sb - len(chunk)
                new_k = k_all[:, j, c0:].astype(jnp.float32)
                new_v = v_all[:, j, c0:].astype(jnp.float32)
                st["mk"] = jnp.concatenate([st["mk"], new_k], axis=1)
                st["mv"] = jnp.concatenate([st["mv"], new_v], axis=1)
                st["ka"] = jnp.maximum(
                    st["ka"], jnp.max(jnp.abs(new_k), axis=(1, 3)))
                st["va"] = jnp.maximum(
                    st["va"], jnp.max(jnp.abs(new_v), axis=(1, 3)))
            rid = st["rid"]
            req = self._reqs[rid]
            if st["done"] < len(req.tokens):
                continue                       # more chunks to go
            # final chunk: the row goes live exactly like a cold admission
            del self._chunk_state[slot]
            entry["rows"].append((j, rid))
            self._register_chunked(rid, st)
            if req.max_new == 1:               # done on arrival
                entry["completes"].append(rid)
                self._release_blocks(st["blocks"])
                if st["entry"] is not None:
                    self.registry.release(st["entry"])
                clear.append(slot)
                continue
            self.slot_req[slot] = rid
            self._slot_crit[slot] = self.policy.bind_critical(req)
            self._slot_level[slot] = self.policy.klass(req).level
            self.remaining[slot] = req.max_new - 1
            self._slot_blocks[slot] = (st["blocks"], st["entry"])
            self._seed_spec(slot, req)
        if clear:
            self._caches = self._clear(self._pad_slot_idx(clear),
                                       self._caches)
        if entry["rows"]:
            self._inflight.append(entry)

    def _register_chunked(self, rid: int, st: dict) -> None:
        """Offer a finished chunked prompt's prefix chain for reuse —
        same chain discipline as :meth:`_register_prefixes`, sourced from
        the row's mapped blocks (kv16; a chunk-from-hit chain includes the
        shared span it mapped) / accumulated masters (int KV)."""
        if self.registry is None:
            return
        t = np.asarray(self._reqs[rid].tokens, np.int32)
        j_max = (len(t) - 1) // self.block_size
        mk = mv = None
        if st["mk"] is not None and j_max >= 1:
            # one master buffer for the whole chain, truncated to the
            # registrable span (entries slice by their own n_tokens)
            mk = st["mk"][:, :j_max * self.block_size]
            mv = st["mv"][:, :j_max * self.block_size]
        self.registry.register_chain(self._prefix_keys.get(rid, []),
                                     j_max, st["map"], mk, mv,
                                     share_blocks=self.srv.scfg.kv_bits
                                     == 16)

    def _post_admission(self, tok0, pname: str, rows) -> None:
        """Common post-dispatch bookkeeping for paged admission waves.

        ``rows``: ``(wave_row, rid, slot, private_blocks, registry_entry)``.
        ``max_new == 1`` rows complete at admission: their blocks go straight
        back to the allocator and their (never-live) slot's block table is
        cleared so residual dead-row writes can't follow the blocks to their
        next owner.
        """
        entry = {"kind": "admit", "toks": tok0, "name": pname,
                 "rows": [], "completes": []}
        clear = []
        for j, rid, slot, blocks, reg in rows:
            req = self._reqs[rid]
            self.results[rid] = {"tokens": [], "profile_trace": []}
            entry["rows"].append((j, rid))
            if self.record_events:
                self.admission_log.append(rid)
            if req.max_new == 1:                             # done on arrival
                entry["completes"].append(rid)
                self._release_blocks(blocks)
                if reg is not None:
                    self.registry.release(reg)
                clear.append(slot)
                continue
            self.slot_req[slot] = rid
            self._slot_crit[slot] = self.policy.bind_critical(req)
            self._slot_level[slot] = self.policy.klass(req).level
            self.remaining[slot] = req.max_new - 1
            self._slot_blocks[slot] = (blocks, reg)
            self._seed_spec(slot, req)
        if clear:
            self._caches = self._clear(self._pad_slot_idx(clear),
                                       self._caches)
        self._inflight.append(entry)

    def _seed_spec(self, slot: int, req, history=None) -> None:
        """Reset slot ``slot``'s speculation state for its new occupant:
        fresh −1 history (the admission flush lands the first token — a
        stale previous occupant's n-grams must never draft for this row)
        and the request's class speculation binding. ``history`` replays a
        resumed row's already-delivered tokens so the drafter warm-starts
        (drafter *quality* only — acceptance verification never depends on
        what was proposed)."""
        if not self.spec:
            return
        self._hist[slot] = -1
        if history:
            h = np.asarray(history[-self._hist.shape[1]:], np.int32)
            self._hist[slot, -len(h):] = h
        self._slot_spec[slot] = self.policy.bind_speculative(req)

    # --------------------------------------------------------------- decoding
    @partial(annotate_function, name="serve.segment")
    def run_segment(self) -> None:
        """One decode segment over the pool: plan ``quantum`` steps against
        the live rows, dispatch the fused scan, distribute tokens, retire.
        A speculative server's segments route to :meth:`_run_segment_spec`
        (same pool, same executable slot, multi-token windows)."""
        if self.spec:
            return self._run_segment_spec()
        q = self.quantum
        mgr = self.srv.manager
        rem = self.remaining
        if mgr is None:
            sched = np.zeros((q,), np.int32)
        elif len(self.policy.classes) > 1:
            # per-class planning: class profile bindings pin the steps a
            # bound row is live for (plus per-request critical flags, which
            # _slot_crit already folds in)
            sched = mgr.plan_schedule_classes(
                q, rem, self._slot_level,
                tuple(c.level for c in self.policy.classes
                      if c.accuracy_critical),
                row_critical=self._slot_crit)
        else:
            sched = mgr.plan_schedule_ragged(q, rem, self._slot_crit)
        if self.record_events:
            for i in range(q):
                live_i = rem > i
                self.events.append((int(sched[i]), int(live_i.sum()),
                                    bool((self._slot_crit & live_i).any())))
        # chaos operand: normally all −1 (never fires, dead data through
        # the one pool-lifetime executable); an armed FaultSchedule poisons
        # a targeted row's logits at the segment's first step
        fault = np.full((self.n_slots,), -1, np.int32)
        if self.faults is not None:
            for slot in range(self.n_slots):
                rid = self.slot_req[slot]
                if rid is None or self.remaining[slot] <= 0:
                    continue
                if self.faults.want_nan(rid, self._attempts.get(rid, 0)):
                    fault[slot] = 0
        toks, ok, self._tok, self._pos, self._caches = self._segment(
            jnp.asarray(sched), self._tok, self._pos, self._caches,
            jnp.asarray(self.remaining, jnp.int32), jnp.asarray(fault))
        # retirement depends only on host-side remaining counts, never on
        # token *values* — so bookkeeping (and the next admission/segment
        # dispatch) proceeds without materializing ``toks``
        entry = {"kind": "seg", "toks": toks, "ok": ok, "sched": sched,
                 "rows": [], "completes": []}
        retired: list[int] = []
        for slot in range(self.n_slots):
            rid = self.slot_req[slot]
            if rid is None:
                continue
            n = int(min(self.remaining[slot], q))
            entry["rows"].append((slot, rid, n))
            self.remaining[slot] -= n
            if self.paged and n:
                self._count_kv(slot, rid)
            if self.remaining[slot] == 0:                # retire → refillable
                self.slot_req[slot] = None
                self._slot_crit[slot] = False
                self._slot_level[slot] = 0
                entry["completes"].append(rid)
                retired.append(slot)
        if self.paged and retired:
            # hand the rows' blocks back (shared prefix blocks just drop one
            # reference; registered private blocks park in the LRU); their
            # block tables need no host dispatch — the segment already
            # unmapped every row that finished inside it (see
            # decode_segment's writeback), so residual dead-row writes
            # can't follow the freed blocks to their next owner
            for slot in retired:
                blocks, reg = self._slot_blocks[slot]
                self._release_blocks(blocks)
                if reg is not None:
                    self.registry.release(reg)
                self._slot_blocks[slot] = None
        self._inflight.append(entry)

    def _run_segment_spec(self) -> None:
        """One *speculative* decode segment: ``ceil(quantum / W)``
        draft/verify windows through the one pool-lifetime spec executable
        (``W = draft_k + 1``).

        Spec mode is synchronous by design: each window's delivered count
        ``m ∈ [1, W]`` is *data* the host needs for retirement, history
        and billing, so the greedy loop's one-segment-ahead overlap is
        traded for multi-token windows (:meth:`step` flushes with
        ``keep=0``). Two consequences land here:

        * the profile plan is **provisional** — per-window ids bind now
          (the schedule rides the scan as data), but the ledger advances
          only at the flush with the tokens each window actually
          delivered (invariant 11: accepted-token billing);
        * retirement and block release move to :meth:`_flush_spec` — the
          host cannot know which rows finished until ``m`` materializes.

        ``quota = quantum`` caps every row's delivered tokens per segment,
        so the fairness quantum is measured in *accepted* tokens no matter
        how lucky the drafter gets.
        """
        self._flush(0)      # land admissions first: fresh rows' history
        w = self.draft_w    # must hold tok0 before their first window
        n_iter = max(1, -(-self.quantum // w))
        mgr = self.srv.manager
        rem = self.remaining
        if mgr is None:
            sched = np.zeros((n_iter,), np.int32)
        elif len(self.policy.classes) > 1:
            sched = mgr.plan_schedule_classes(
                n_iter, rem, self._slot_level,
                tuple(c.level for c in self.policy.classes
                      if c.accuracy_critical),
                row_critical=self._slot_crit, draft_w=w, provisional=True)
        else:
            sched = mgr.plan_schedule_ragged(n_iter, rem, self._slot_crit,
                                             draft_w=w, provisional=True)
        if self.record_events:
            # events mirror the greedy convention — the PLANNED clamped
            # bill per window (what the provisional planner fed select());
            # the tokens actually billed land in ``spec_billed`` at flush,
            # so a replay oracle reproduces both halves exactly
            for i in range(n_iter):
                live_i = rem > i * w
                self.events.append(
                    (int(sched[i]),
                     int(np.minimum(w, np.maximum(rem - i * w, 0)).sum()),
                     bool((self._slot_crit & live_i).any())))
        fault = np.full((self.n_slots,), -1, np.int32)
        if self.faults is not None:
            for slot in range(self.n_slots):
                rid = self.slot_req[slot]
                if rid is not None and self.remaining[slot] > 0 and \
                        self.faults.want_nan(rid,
                                             self._attempts.get(rid, 0)):
                    fault[slot] = 0
        quota = np.full((self.n_slots,), self.quantum, np.int32)
        toks, ms, ok, self._tok, self._pos, self._caches = self._segment(
            jnp.asarray(sched), jnp.asarray(self._hist),
            jnp.asarray(self._slot_spec), self._tok, self._pos,
            self._caches, jnp.asarray(self.remaining, jnp.int32),
            jnp.asarray(quota), jnp.asarray(fault))
        self._inflight.append({
            "kind": "spec", "toks": toks, "ms": ms, "ok": ok,
            "sched": sched, "crit": self._slot_crit.copy(),
            "rows": [(s, self.slot_req[s]) for s in range(self.n_slots)
                     if self.slot_req[s] is not None],
            "completes": []})

    def _flush_spec(self, e: dict, arr: np.ndarray,
                    okarr: Optional[np.ndarray], names) -> None:
        """Materialize one speculative segment entry (the ``keep=0`` sync
        point): distribute each window's delivered prefix, bill the ledger
        the tokens actually delivered (the dispatch plan was provisional —
        invariant 11), slide each row's drafter history, then retire rows
        whose budget hit zero and hand their blocks back. Rows whose
        verify windows went non-finite route to quarantine exactly like
        greedy segments."""
        # repro: allow(host-sync) ready with toks, materialized by the caller
        ms = np.asarray(e["ms"])                          # [B, n_iter]
        mgr = self.srv.manager
        sched = e["sched"]
        n_iter = ms.shape[1]
        h = self._hist.shape[1]
        for i in range(n_iter):
            n_tok = int(ms[:, i].sum())   # idle rows deliver 0: full sum
            if mgr is not None:
                mgr.account(int(sched[i]), n_tok)
            if self.record_events:
                self.spec_billed.append((int(sched[i]), n_tok))
        retired: list[int] = []
        for slot, rid in e["rows"]:
            res = self.results[rid]
            delivered: list[int] = []
            for i in range(n_iter):
                m = int(ms[slot, i])
                if m:
                    delivered.extend(arr[slot, i, :m].tolist())
                    res["profile_trace"].extend([names[sched[i]]] * m)
            res["tokens"].extend(delivered)
            if delivered:
                cat = np.concatenate([self._hist[slot],
                                      np.asarray(delivered, np.int32)])
                self._hist[slot] = cat[-h:]
            if okarr is not None and delivered and not okarr[slot] \
                    and rid not in self._nf_rows:
                self._nf_rows.append(rid)
            self.remaining[slot] -= len(delivered)
            if self.paged and delivered:
                self._count_kv(slot, rid)
            if self.remaining[slot] == 0 and delivered:
                self.slot_req[slot] = None               # retire → refill
                self._slot_crit[slot] = False
                self._slot_level[slot] = 0
                e["completes"].append(rid)
                retired.append(slot)
        if self.paged and retired:
            # same contract as greedy retirement: the spec segment already
            # unmapped finished rows in-graph (decode_segment_spec's
            # `finish` writeback), so freed blocks can't take dead writes
            for slot in retired:
                blocks, reg = self._slot_blocks[slot]
                self._release_blocks(blocks)
                if reg is not None:
                    self.registry.release(reg)
                self._slot_blocks[slot] = None

    @partial(annotate_function, name="serve.flush")
    def _flush(self, keep: int = 0) -> None:
        """Materialize in-flight token blocks into per-request results.

        ``keep`` leaves the newest entries un-synced: with ``keep=1`` the
        engine loop runs one segment ahead of the host sync, so planning,
        admission bookkeeping, and the next dispatch overlap device compute
        (async dispatch) instead of serializing on ``np.asarray`` per segment.
        A request counts as completed only once its tokens are materialized.

        The flush boundary is also where fault *detection* lands on the
        host: each segment entry carries its per-row finite-check flags,
        and a live row that went non-finite is routed to quarantine
        (:meth:`_process_quarantine`) instead of completing.
        ``serve.flush.wait`` spans the host blocked on each entry's arrays.
        """
        if self.faults is not None and len(self._inflight) > keep:
            s = self.faults.flush_stall(self._flush_idx)
            self._flush_idx += 1
            if s > 0.0:
                time.sleep(s)            # injected stall: watchdog fodder
        names = self.srv.engine.profile_names
        drained = len(self._inflight) > keep
        while len(self._inflight) > keep:
            e = self._inflight.pop(0)
            with TraceAnnotation("serve.flush.wait"):
                # repro: allow(host-sync) the flush boundary IS the sync point
                arr = np.asarray(e["toks"])              # blocks until ready
                # repro: allow(host-sync) flush-boundary sync, same as toks
                okarr = (np.asarray(e["ok"])
                         if e.get("ok") is not None else None)
            if e["kind"] == "admit":
                for j, rid in e["rows"]:
                    res = self.results[rid]
                    res["tokens"].append(int(arr[j]))
                    res["profile_trace"].append(e["name"])
                    if self.spec:
                        # the admission token is the row's current token:
                        # it lands in the history's last slot (the n-gram
                        # drafter convention) before the first window runs
                        try:
                            self._hist[self.slot_req.index(rid), -1] = \
                                int(arr[j])
                        except ValueError:
                            pass         # max_new == 1: never went live
            elif e["kind"] == "spec":
                self._flush_spec(e, arr, okarr, names)
            else:
                for slot, rid, n in e["rows"]:
                    res = self.results[rid]
                    res["tokens"].extend(arr[slot, :n].tolist())
                    res["profile_trace"].extend(
                        names[p] for p in e["sched"][:n])
                    if okarr is not None and n > 0 and not okarr[slot] \
                            and rid not in self._nf_rows:
                        self._nf_rows.append(rid)
            for rid in e["completes"]:
                if rid in self._nf_rows:
                    continue             # quarantine owns this row now
                res = self.results[rid]
                res["status"] = RequestStatus.COMPLETED
                if rid in self._attempts:
                    res["retries"] = self._attempts[rid]
                if rid in self._q_t0:
                    self.recovery_latency.append(
                        self.clock() - self._q_t0.pop(rid))
                    self.recovered += 1
                self._done.append(rid)
                if self.durable is not None:
                    self.durable.on_final(rid)
        if drained and self.durable is not None:
            self.durable.on_flush()      # crash-point / consistency-cut mark

    # ------------------------------------------------------------------ drive
    @partial(annotate_function, name="serve.step")
    def step(self) -> bool:
        """One engine round: retire deadline/cancel/fault casualties, then
        admit and run one segment (one kept in flight). Returns False once
        fully drained (all tokens materialized, no pending retries).
        Mid-admission chunked rows, suspended (preempted) requests, and
        quarantine-backoff retries keep the loop alive."""
        self._round += 1
        t0 = self.clock()
        self._expire()
        self._reap_marked()
        self._process_quarantine()
        if self._quarantine_q:
            ripe = [(r, rid) for r, rid in self._quarantine_q
                    if r <= self._round]
            if ripe:
                self._quarantine_q = [x for x in self._quarantine_q
                                      if x[0] > self._round]
                for _, rid in reversed(ripe):    # preserve relative order
                    self.policy.push_front(rid, self._reqs[rid])
        self.policy.age_tick()           # anti-starvation promotion (if on)
        n_adm = self.admit()
        if n_adm and self.durable is not None:
            self.durable.on_admit(n_adm)
        ran = False
        if self.live_rows:
            self.run_segment()
            # spec mode is synchronous (delivered counts gate retirement);
            # greedy keeps one segment in flight to overlap host + device
            self._flush(keep=0 if self.spec else 1)
            ran = True
        else:
            self._flush()
        dt = self.clock() - t0
        if ran:         # EMA over rounds that actually ran a segment
            self._seg_dt = (dt if self._seg_dt is None
                            else 0.5 * dt + 0.5 * self._seg_dt)
        if self.durable is not None:
            self.durable.on_step_end()   # checkpoint cadence hook
        if self.watchdog is not None:
            self.watchdog.record(f"round {self._round}", dt)
        if self.paranoid:
            self.check()
        return bool(self.live_rows or len(self.policy) or self._inflight
                    or (self.paged and self._chunk_state)
                    or self._to_reap or self._nf_rows or self._quarantine_q)

    def run(self) -> list[dict]:
        """Drain queue + pool; results in submission order (entries already
        claimed through poll_completed come back as None)."""
        while self.step():
            pass
        return [self.results.get(i) for i in range(self._n)]

    def drain(self) -> None:
        """Graceful-shutdown drain: stop admitting new work, then step the
        pool until every already-admitted row (live, chunked, in-flight,
        reaped, quarantined) has reached a terminal status. Queued-but-
        never-admitted requests stay queued — a durability layer
        checkpoints them for the next process; without one the caller
        still holds their journal/submission record. The SIGTERM handler
        in ``launch/serve.py`` drives this."""
        self.draining = True
        if self.durable is not None:
            self.durable.on_drain()
        while (self.live_rows or self._inflight
               or (self.paged and self._chunk_state)
               or self._to_reap or self._nf_rows or self._quarantine_q):
            self.step()
