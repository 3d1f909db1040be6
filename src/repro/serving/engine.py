"""Adaptive serving engine: batched prefill + fused on-device decode loop.

The FPGA paper's runtime (Fig. 4 left) = Adaptive Inference Engine + Profile
Manager. Here the engine is a pair of jitted functions closed over the merged
profile family (profile_id is a traced scalar → switching never recompiles),
and the manager picks the profile per decode step from the energy budget.

**Scan/donation design.** Decode runs as a single jitted ``jax.lax.scan`` over
the generation length (:func:`repro.models.transformer.decode_many`):

* one dispatch per ``generate`` call — greedy argmax sampling, KV/SSM cache
  updates, and profile switching all stay on device; the only host sync is
  one ``np.asarray`` of the final ``[B, steps]`` token block (the seed
  engine synced + re-dispatched per token);
* the KV caches are threaded through the scan carry and **donated** at the
  ``jit`` boundary (``donate_argnums``), so XLA updates the cache buffers in
  place instead of copying them every step;
* profile adaptivity survives fusion: the :class:`ProfileManager` budget
  policy is deterministic given its energy ledger, so the per-step profile
  ids are precomputed as an ``int32[steps]`` schedule
  (``ProfileManager.plan_schedule``) and fed to the scan as *data* — the
  merged engine stays branch-free and a new schedule never retraces. The
  realized per-step trace comes back from the device for accounting.

``generate_stepwise`` keeps the seed per-token host loop as the benchmark
baseline (``benchmarks/serving_bench.py`` measures the tokens/sec win).

KV cache precision is a deployment knob (``kv_bits``: 16 = bf16 baseline,
8 = int8 — the beyond-paper memory-roofline win; the Pallas
``qkv_attention`` kernel is the TPU path for the int8 layout, and the jnp
decode path contracts on the same int8 grid).
"""
from __future__ import annotations

import dataclasses
import enum
import logging
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.engine import AdaptiveEngine
from repro.core.manager import ProfileManager, ProfileStats
from repro.models import transformer as T

__all__ = ["ServingConfig", "AdaptiveServer", "Request", "RequestStatus"]


class RequestStatus(str, enum.Enum):
    """Terminal outcome of one request — the single enum every lifecycle
    path resolves to on ``poll_completed`` results (``result["status"]``).

    ``COMPLETED`` — all ``max_new`` tokens delivered. ``CANCELLED`` — client
    cancellation (:meth:`~repro.serving.scheduler.ContinuousScheduler.
    cancel`); tokens generated before the cancel are delivered. ``EXPIRED``
    — the request's ``deadline_ms`` passed (in queue, mid-generation, or
    rejected up front as unreachable at admission — ``result["reason"]``
    says which). ``SHED`` — dropped by the overload shedding policy
    (:class:`~repro.serving.policy.ShedPolicy`) instead of queueing
    unboundedly. ``FAILED`` — produced non-finite output on every attempt
    of the quarantine/precision-fallback retry ladder. Values are plain
    strings (``str`` subclass) so results serialize to JSON untouched.
    """

    COMPLETED = "completed"
    CANCELLED = "cancelled"
    EXPIRED = "expired"
    SHED = "shed"
    FAILED = "failed"


def _next_pow2(n: int) -> int:
    """Smallest power of two ≥ ``n`` (shape-bucketing helper)."""
    return 1 << (int(n) - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Deployment knobs for an :class:`AdaptiveServer`.

    ``slots`` — per-row KV capacity in tokens; must cover ``prompt_len +
    max_new`` for every request (sliding-window stacks ring within their
    window regardless). ``kv_bits`` — KV cache storage precision: 16 (bf16
    baseline), 8 (int8, the beyond-paper memory-roofline win) or 4 (packed
    int4, two nibbles per byte — half of kv8's pool bytes, 2× its token
    capacity; the paged-attention kernel unpacks in VMEM). ``max_batch``
    — decode rows: the static group width of :meth:`AdaptiveServer.serve` and
    the slot-pool size of :class:`~repro.serving.scheduler.
    ContinuousScheduler`. ``greedy`` — argmax sampling (the only mode the
    fused decode scan implements today).

    Paged-KV knobs (used by the continuous scheduler's slot pool; the
    solo/static paths keep the contiguous layout as the oracle):

    ``paged_kv`` — lay the pool out as a global block pool + per-row block
    tables instead of contiguous ``[max_batch, slots]`` rows.
    ``block_size`` — tokens per block (rounded down to a divisor of the
    sliding window when one exists, so paged placement matches the
    contiguous ring exactly). ``pool_blocks`` — physical blocks to
    provision; ``None`` means ``max_batch * ceil(slots/block_size)``, the
    exact contiguous footprint — set it lower to realize the paged memory
    win (short rows + shared prefixes), with admission backpressure as the
    safety valve. ``prefix_cache`` — register block-aligned prompt prefixes
    and serve hash-matched admissions from them (full-attention stacks
    only); ``prefix_capacity`` bounds registered entries (LRU — note one
    prompt registers its whole block-aligned prefix chain, one entry per
    length, so later prompts can match at any block boundary).
    ``paged_backend`` — how decode reads the paged pool: ``"pallas"``
    attends in place against the blocks through the paged-attention kernel
    (no dense view, no fold-back — the serving hot path; an interleaved
    stack's recurrent state is then updated in place by the
    ``ssm_decode_pallas`` kernel too), ``"gather"`` materializes the
    per-segment dense view (the CPU oracle path), ``"auto"`` picks pallas
    on TPU and gather elsewhere. ``prefill_chunk``
    — when set, admission prompts longer than this many tokens prefill in
    block-aligned chunks that interleave with decode segments instead of
    one monolithic wave (full-causal stacks only), smoothing the
    admission-wave latency spike; ``None`` disables chunking.

    Scheduling-policy knobs (:mod:`repro.serving.policy`):

    ``priority_classes`` — number of request priority classes; 1 keeps the
    classless FIFO, ≥2 builds the stock ladder (class 0 = ``critical``:
    admitted first and profile-bound to the accuracy target; the last
    class = ``saver``: preemptible). Requests pick their class with
    :attr:`Request.priority`. ``preemption`` — arm preemptive scheduling:
    a critical arrival that cannot admit (no free slot, or the block
    allocator is dry) evicts saver-class rows — their block tables and
    host-side KV masters are snapshotted (:meth:`~repro.serving.scheduler.
    ContinuousScheduler.evict_row`) and they resume later through the
    continuation-prefill executable, token-identically. Requires the paged
    pool on a ``supports_prefix_sharing`` stack. ``aging`` — anti-
    starvation promotion age in scheduler rounds: a queued class head
    that has waited this many rounds is promoted one level up the
    ladder (queue position only — profile binding, billing and
    preemption keep the request's class); ``None`` keeps strict
    lowest-level-first.

    Speculative-decoding knobs (docs/serving.md §Speculation):

    ``speculate`` — decode through draft/verify windows: each segment
    iteration proposes ``draft_k`` tokens per row and verifies the
    ``draft_k + 1`` window in ONE batched forward
    (:func:`repro.models.transformer.decode_segment_spec`), delivering
    1..``draft_k + 1`` tokens per row per iteration — **token-identical**
    to non-speculative greedy at kv16 and kv8, it only changes
    throughput. Requires a ``supports_speculation`` stack (full causal
    attention, kv16/kv8). The pool-lifetime ``_segment`` executable IS
    the speculative one on such a server: still exactly one decode
    executable, zero per-token dispatches. ``draft_k`` — drafted tokens
    per window. ``draft_hist`` — token-history length the self-
    speculative n-gram drafter sees (a host-side ``[max_batch,
    draft_hist]`` operand, updated at the flush boundary).
    ``draft_model`` — which drafter proposes: ``None``/``"ngram"`` = the
    built-in majority-vote follower n-gram drafter, ``"repeat"`` = repeat
    the current token (the degenerate run-length drafter). External
    small-model drafters plug in as a traced ``draft_fn(hist, tok) ->
    [B, draft_k]`` via :class:`AdaptiveServer`'s ``draft_fn`` argument.

    Durability knob (docs/serving.md §Durability):

    ``kv16_masters`` — keep full-precision (f32) KV masters for shared
    prefixes and chunked rows even at ``kv_bits=16``. The bf16 pool is
    normally its own master (shared admissions gather the prefix straight
    from the shared blocks), which is token-identical but not
    *structurally* bit-exact: a continuation attends over bf16-rounded
    prefix values where a cold prefill attends over the raw f32 ones.
    With masters on, every continuation path (shared, chunked, restore)
    replays the prefix from the raw activations — the same structural
    bit-exactness the preemption-restore path has at int KV — and durable
    checkpoints snapshot exact row state at kv16. Costs host memory
    (f32 masters per registry entry / in-flight chunk row); identity of
    delivered tokens does not depend on it. Only meaningful at
    ``kv_bits=16`` — int pools (kv8/kv4) already keep masters, and the
    combination is rejected at construction.

    Precision-policy knob (docs/serving.md §Precision ladder):

    ``precision_policy`` — per-profile, per-layer KV bit-width schedule: a
    ``[n_profiles, n_layers]`` nested tuple of entries in (4, 8, 16),
    typically searched offline against the accuracy-vs-bytes frontier
    (:meth:`repro.core.manager.ProfileManager.search_precision` /
    ``benchmarks/precision_frontier.py``). The table rides the executables
    as **data** (rows gathered by the traced profile id), so profile
    switches never retrace; entries of 16 are exact passthrough, which is
    how a ``critical``-bound profile row pins the hand-set baseline
    token-identically while ``saver`` profiles ride the searched frontier.
    ``None`` (default) disables the policy with a byte-identical lowering.
    Incompatible with ``speculate`` (draft/verify windows do not thread
    the per-layer schedule).
    """

    slots: int = 4096
    kv_bits: int = 16
    max_batch: int = 8
    greedy: bool = True
    paged_kv: bool = True
    block_size: int = 16
    pool_blocks: Optional[int] = None
    prefix_cache: bool = True
    prefix_capacity: int = 32
    paged_backend: str = "auto"
    prefill_chunk: Optional[int] = None
    priority_classes: int = 1
    preemption: bool = False
    aging: Optional[int] = None
    speculate: bool = False
    draft_k: int = 4
    draft_hist: int = 32
    draft_model: Optional[str] = None
    kv16_masters: bool = False
    precision_policy: Optional[tuple] = None


@dataclasses.dataclass
class Request:
    """One generation request.

    ``tokens`` — the ``[S]`` int32 prompt. ``max_new`` — token budget; the
    request retires after exactly ``max_new`` generated tokens (greedy, no
    EOS short-circuit). ``accuracy_critical`` — pins profile selection to
    the accuracy target even in the battery-saver regime (paper §4.4).
    ``priority`` — priority-class index under a class-aware scheduling
    policy (0 = most urgent, clamped into the configured ladder; ignored
    by the classless FIFO). Class membership also binds the profile
    policy: rows of an accuracy-critical class pin selection like
    ``accuracy_critical`` does. ``deadline_ms`` — optional client SLO in
    milliseconds from submission: the scheduler expires the request
    (``RequestStatus.EXPIRED``) if the deadline passes while it is queued
    or mid-generation, and rejects it up front at admission when the
    current throughput estimate says it cannot finish in time.
    """

    tokens: np.ndarray
    max_new: int = 32
    accuracy_critical: bool = False
    priority: int = 1
    deadline_ms: Optional[float] = None


class _BoundWeights:
    """A jitted serving primitive whose leading arguments — the server's
    lifetime weights — are bound here instead of closed over.

    ``jax.jit`` embeds a closed-over array in the executable as a literal
    constant: at full width that is gigabytes of HLO to build and compile.
    Bound weights stay device buffers passed at every dispatch, which costs
    one flatten of a pytree of a few dozen leaves. ``donate_argnums``
    index the unbound arguments.

    Each dispatch is a ``serve.dispatch.<fn>`` profiler span: the host
    time to flatten the arguments and enqueue the executable.
    """

    def __init__(self, fn, weights: tuple, donate_argnums: tuple = ()):
        n = len(weights)
        self.jitted = jax.jit(
            fn, donate_argnums=tuple(n + i for i in donate_argnums))
        self.weights = weights
        self.span = "serve.dispatch." + fn.__name__.lstrip("_")

    def __call__(self, *args):
        with TraceAnnotation(self.span):
            return self.jitted(*self.weights, *args)

    def lower(self, *args):
        return self.jitted.lower(*self.weights, *args)

    def _cache_size(self) -> int:
        return self.jitted._cache_size()


class AdaptiveServer:
    """Adaptive inference engine: jitted serving entry points over one model.

    Owns the compiled executables of the serving stack — ``_prefill`` /
    ``_decode`` (stepwise oracle), ``_generate`` (fused whole-generation
    scan), and the continuous-batching primitives ``_segment`` / ``_admit``
    (+ paged variants) shared by every :class:`~repro.serving.scheduler.
    ContinuousScheduler` built on top — plus the prequantized decode
    weight images (one per distinct weight-width row of the profiles). Profile adaptivity is bits-as-data: ``profile_id`` and
    per-step schedules are traced int32 inputs, so switching profiles never
    recompiles (the paper's runtime configuration word).

    Args:
        cfg: model architecture.
        params: parameter pytree (fixed for the server's lifetime — the
            prequant images and the executables bound to it assume it).
        engine: merged :class:`AdaptiveEngine` (profile family + bits table).
        serving: :class:`ServingConfig` deployment knobs.
        manager: optional :class:`ProfileManager`; ``None`` pins profile 0.
    """

    def __init__(self, cfg: T.ModelConfig, params, engine: AdaptiveEngine,
                 serving: ServingConfig,
                 manager: Optional[ProfileManager] = None,
                 draft_fn=None):
        """Compile the serving executables and prequantize weight images
        (see the class docstring for the argument contract). ``draft_fn``
        overrides the speculative drafter: a traced ``(hist [B, H], tok
        [B]) -> proposals [B, draft_k]`` callable (external small-model
        drafters); ``None`` defers to ``ServingConfig.draft_model``."""
        self.cfg = cfg
        self.params = params
        self.engine = engine
        self.scfg = serving
        self.manager = manager
        table = engine.table
        if serving.kv_bits not in (4, 8, 16, 32):
            raise ValueError(f"kv_bits must be 4, 8, 16 or 32, "
                             f"got {serving.kv_bits}")
        if serving.kv16_masters and serving.kv_bits != 16:
            raise ValueError(
                "kv16_masters only applies to bf16 pools (kv_bits=16): "
                f"a kv{serving.kv_bits} pool is lossy and always keeps "
                "full-precision masters")
        if serving.speculate:
            if not T.supports_speculation(cfg, serving.kv_bits):
                raise ValueError(
                    "speculate=True needs a supports_speculation stack: "
                    "full causal attention (no SSM/MoE/sliding-window) "
                    "with kv_bits in (8, 16)")
            if serving.draft_k < 1:
                raise ValueError("draft_k must be >= 1")
            if serving.draft_hist < 2:
                raise ValueError("draft_hist must be >= 2 (the n-gram "
                                 "drafter votes over history pairs)")
        if draft_fn is None:
            if serving.draft_model in (None, "ngram"):
                pass                     # decode_segment_spec's built-in
            elif serving.draft_model == "repeat":
                def draft_fn(hist, tok):
                    return jnp.broadcast_to(tok[:, None],
                                            (tok.shape[0], serving.draft_k))
            else:
                raise ValueError(f"unknown draft_model "
                                 f"{serving.draft_model!r}: use None, "
                                 f"'ngram' or 'repeat' (or pass draft_fn)")
        self.draft_fn = draft_fn

        # ---- per-layer precision policy (kv_table) -----------------------
        # precision as a policy OUTPUT: each profile binds an int32[L] row
        # of per-layer KV bit-widths. The [P, L] table is a server-lifetime
        # constant the executables close over; rows are gathered by the
        # *traced* profile id, so schedule/profile switches never retrace —
        # the same bits-as-data trick as the engine's quant table. With no
        # policy every call site passes kv_sched=None and the lowering is
        # byte-identical to the policy-free engine.
        kv_table = None
        if serving.precision_policy is not None:
            if serving.speculate:
                raise ValueError(
                    "precision_policy is incompatible with speculate=True: "
                    "draft/verify windows do not thread the per-layer KV "
                    "schedule")
            pol = np.asarray(serving.precision_policy, np.int32)
            n_prof = len(engine.profile_names)
            if pol.shape != (n_prof, cfg.n_layers):
                raise ValueError(
                    f"precision_policy must have shape [n_profiles="
                    f"{n_prof}, n_layers={cfg.n_layers}], got "
                    f"{tuple(pol.shape)}")
            if not np.isin(pol, (4, 8, 16)).all():
                raise ValueError(
                    "precision_policy entries must be 4, 8 or 16")
            kv_table = jnp.asarray(pol)
        self.kv_table = kv_table

        def prefill_fn(params, profile_id, batch):
            bits = jnp.asarray(table)[profile_id]
            ks = None if kv_table is None else kv_table[profile_id]
            return T.prefill(params, cfg, bits, batch, serving.slots,
                             kv_bits=serving.kv_bits, kv_sched=ks)

        def decode_fn(params, profile_id, tokens, pos, caches):
            bits = jnp.asarray(table)[profile_id]
            ks = None if kv_table is None else kv_table[profile_id]
            return T.decode_step(params, cfg, bits, tokens, pos, caches,
                                 kv_sched=ks)

        def generate_fn(params, prequant, schedule, logits0, pos0, caches,
                        row_budget):
            return T.decode_many(params, cfg, jnp.asarray(table), schedule,
                                 logits0, pos0, caches, row_budget=row_budget,
                                 prequant=prequant, kv_table=kv_table)

        # ---- paged decode backend ----------------------------------------
        # "pallas" = in-place paged-attention kernel (interpret mode off-TPU,
        # compiled on TPU); "gather" = per-segment dense view, the oracle.
        # kv4/kv8/kv16 all have a kernel path (kv4 unpacks its nibbles in
        # VMEM); any other precision degrades to gather — loudly.
        pb = serving.paged_backend
        if pb not in ("auto", "pallas", "gather"):
            raise ValueError(f"paged_backend must be auto|pallas|gather, "
                             f"got {pb!r}")
        if pb == "auto":
            pb = "pallas" if jax.default_backend() == "tpu" else "gather"
        if pb == "pallas" and serving.kv_bits not in (4, 8, 16):
            logging.getLogger("repro.serving").warning(
                "paged_backend degraded pallas -> gather: kv_bits=%d has "
                "no paged-attention kernel path (kv4/kv8/kv16 only)",
                serving.kv_bits)
            pb = "gather"
        self.paged_backend = pb

        # params / prequant are server-lifetime constants: the continuous
        # primitives take them as leading arguments that _BoundWeights binds,
        # never as closed-over constants (see _BoundWeights)
        def segment_fn(params, prequant, schedule, tok, pos, caches,
                       remaining, fault_step):
            # fault_step [B] is DATA (normally all −1): the chaos machinery's
            # NaN-injection operand plus the per-row finite-check flag ride
            # the one pool-lifetime segment executable — detection and
            # injection never add a dispatch or a recompile
            return T.decode_segment(params, cfg, jnp.asarray(table),
                                    schedule, tok, pos, caches, remaining,
                                    prequant=prequant,
                                    paged_backend=self.paged_backend,
                                    fault_step=fault_step,
                                    kv_table=kv_table)

        def segment_spec_fn(params, prequant, schedule, hist, spec_on, tok,
                            pos, caches, remaining, quota, fault_step):
            # speculative pool-lifetime segment: len(schedule) draft/verify
            # windows; hist/spec_on/quota are per-dispatch DATA operands
            # (host token history, per-class opt-out, quantum in accepted
            # tokens) — same zero-recompile contract as the greedy segment
            return T.decode_segment_spec(params, cfg, jnp.asarray(table),
                                         schedule, tok, pos, caches,
                                         remaining, quota=quota, hist0=hist,
                                         spec_on=spec_on,
                                         prequant=prequant,
                                         paged_backend=self.paged_backend,
                                         fault_step=fault_step,
                                         draft_k=serving.draft_k,
                                         draft_fn=self.draft_fn)

        def admit_fn(params, profile_id, batch, slots_idx, tok, pos, caches):
            # one admission wave = one dispatch: ragged prefill of every
            # waiting request (left-padded to a shared pow2 bucket,
            # ``prompt_len`` as data) + on-device first-token argmax + scatter
            # of each prefilled row into its pool slot. Rows whose
            # ``slots_idx`` is out of range (admission-batch padding) are
            # dropped by the scatter. The WHOLE pool row is overwritten
            # (batch axis 1 under the [L, ...] layer stacking): stale
            # token_idx entries of a retired request must not survive into
            # the new request's attention window.
            bits = jnp.asarray(table)[profile_id]
            ks = None if kv_table is None else kv_table[profile_id]
            logits, rows = T.prefill(params, cfg, bits, batch,
                                     serving.slots, kv_bits=serving.kv_bits,
                                     kv_sched=ks)
            tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            caches = jax.tree.map(
                lambda pool, row: pool.at[:, slots_idx].set(row, mode="drop"),
                caches, rows)
            return (tok0,
                    tok.at[slots_idx].set(tok0, mode="drop"),
                    pos.at[slots_idx].set(
                        jnp.asarray(batch["prompt_len"], jnp.int32),
                        mode="drop"),
                    caches)

        # ---- paged-KV geometry (continuous scheduler's block pool) -------
        # block size degrades to a divisor of the SWA window so paged ring
        # placement matches the contiguous ring slot-for-slot
        self.block_size = T.paged_block_size(cfg, serving.slots,
                                             serving.block_size)
        eff = (min(serving.slots, cfg.sliding_window) if cfg.sliding_window
               else serving.slots)
        self.n_lblk = -(-eff // self.block_size)       # logical blocks / row
        self.slots_p = self.n_lblk * self.block_size   # virtual row length
        self.prefix_sharing = bool(serving.prefix_cache
                                   and T.supports_prefix_sharing(cfg))
        # chunked prefill rides the continuation-prefill machinery
        # (prefill_extend at absolute positions), which is exact only where
        # prefix sharing is: full causal attention, no SSM/MoE coupling.
        # Chunk length rounds down to a block multiple so every chunk
        # boundary is a block boundary (the kv16 path gathers the processed
        # prefix straight from the row's own whole blocks).
        self.chunk_tokens: Optional[int] = None
        if serving.prefill_chunk and T.supports_prefix_sharing(cfg):
            self.chunk_tokens = max(
                self.block_size,
                (int(serving.prefill_chunk) // self.block_size)
                * self.block_size)
        # full-precision prefix masters are needed when the pool's storage
        # is lossy (int KV): a bf16 pool *is* its own master, so kv16 shared
        # admissions gather the prefix straight from the shared blocks and
        # the registry stores nothing but block ids. Chunked prefill needs
        # them for the same reason (each chunk replays the previous ones as
        # its prefix). ``kv16_masters`` opts a bf16 pool into the same
        # master-backed continuations (structural bit-exactness + exact
        # durable snapshots at kv16 — see the ServingConfig docstring).
        self.masters_mode = (serving.kv_bits != 16
                             or bool(serving.kv16_masters))
        self._collect_masters = self.masters_mode and bool(
            self.prefix_sharing or self.chunk_tokens)

        def admit_paged_fn(params, prequant, profile_id, batch, slots_idx,
                           dest, tok, pos, caches):
            # paged admission wave: one ragged prefill into transient dense
            # rows, then one scatter of those rows into the block pool at
            # the host-chosen physical ids. ``dest[j, l]`` is the write
            # mapping for row j's logical block l — out-of-range entries
            # (wave padding, logical blocks past the row's need, and shared
            # prefix blocks owned by the registry) are DROPPED by the
            # scatter: that drop is the copy-on-write discipline. Writing
            # every private block wholesale also clears any stale
            # ``token_idx`` left by the block's previous owner.
            bits = jnp.asarray(table)[profile_id]
            ks = None if kv_table is None else kv_table[profile_id]
            # the recurrent state (if any) goes straight to the pool rows;
            # an interleaved stack prefills from the weight image
            out = T.prefill(params, cfg, bits, batch, self.slots_p,
                            kv_bits=serving.kv_bits,
                            return_raw_kv=self._collect_masters,
                            kv_sched=ks,
                            ssm_into=(caches["ssm"], slots_idx)
                            if "ssm" in caches else None,
                            images=(prequant, profile_id))
            logits, rows = out[0], out[1]
            raw = out[2] if self._collect_masters else None
            tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            caches = dict(caches)
            caches["kv"] = self._scatter_blocks(caches["kv"], rows["kv"],
                                                dest, slots_idx)
            if "ssm" in caches:
                caches["ssm"] = rows["ssm"]
            plen = jnp.asarray(batch["prompt_len"], jnp.int32)
            return (tok0, raw,
                    tok.at[slots_idx].set(tok0, mode="drop"),
                    pos.at[slots_idx].set(plen, mode="drop"),
                    caches)

        def _admit_shared_body(params, profile_id, batch, slots_idx, dest,
                               bt_rows, kpre, vpre, ka, va, prefix_len, tok,
                               pos, caches):
            # shared-prefix admission wave: continuation prefill over the
            # suffixes only (prefix KV replayed from masters / pool
            # blocks), then the same block scatter — with ``dest``
            # out-of-range on the shared blocks (never written; ``bt_rows``
            # still maps them) and private on everything after the
            # divergence point: that skipped write IS the copy-on-write.
            # Chunked prefill reuses this executable verbatim: a chunk's
            # "prefix" is simply the row's own previously processed chunks.
            bits = jnp.asarray(table)[profile_id]
            ks = None if kv_table is None else kv_table[profile_id]
            out = T.prefill_extend(
                params, cfg, bits, batch, self.slots_p,
                kv_bits=serving.kv_bits, prefix_k=kpre, prefix_v=vpre,
                prefix_len=prefix_len, prefix_k_amax=ka, prefix_v_amax=va,
                return_raw_kv=self._collect_masters, kv_sched=ks)
            logits, rows = out[0], out[1]
            raw = out[2] if self._collect_masters else None
            tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            caches = dict(caches)
            caches["kv"] = self._scatter_blocks(caches["kv"], rows["kv"],
                                                dest, slots_idx,
                                                bt_rows=bt_rows)
            plen = jnp.asarray(prefix_len, jnp.int32) + \
                jnp.asarray(batch["prompt_len"], jnp.int32)
            return (tok0, raw,
                    tok.at[slots_idx].set(tok0, mode="drop"),
                    pos.at[slots_idx].set(plen, mode="drop"),
                    caches)

        def admit_shared_pool_fn(params, profile_id, batch, slots_idx, dest,
                                 bt_rows, pre_bids, prefix_len, tok, pos,
                                 caches):
            # bf16 variant: the shared pool blocks ARE the masters — gather
            # the prefix KV straight from them (zero duplicated storage)
            pool = caches["kv"]
            a, pb = pre_bids.shape

            def gather(x):                     # [L, nb, bs, Hkv, hd]
                g = jnp.take(x, pre_bids, axis=1, mode="fill", fill_value=0)
                return g.reshape(cfg.n_layers, a, pb * x.shape[2],
                                 *x.shape[3:]).astype(jnp.float32)

            return _admit_shared_body(params, profile_id, batch, slots_idx,
                                      dest, bt_rows, gather(pool.k),
                                      gather(pool.v), None, None,
                                      prefix_len, tok, pos, caches)

        def clear_rows_fn(slots_idx, caches):
            # retirement: unmap the rows' block tables so a retired row's
            # residual junk writes (dead rows keep stepping inside a
            # segment) can never land in a block that has been reallocated
            pool = caches["kv"]
            nb = pool.k.shape[1]           # [L, n_blocks, bs, ...]
            bt = pool.block_table.at[:, slots_idx].set(nb, mode="drop")
            return {**caches, "kv": pool._replace(block_table=bt)}

        self._prefill = jax.jit(prefill_fn)
        self._decode = jax.jit(decode_fn,
                               donate_argnums=(4,))        # stepwise baseline
        # decode weight images, materialized once per server (params and the
        # profile table are fixed for its lifetime)
        self._prequant = jax.jit(
            lambda p: T.prequant_decode_weights(p, cfg, jnp.asarray(table))
        )(params)
        # donate the caches: the scan threads them through its carry and XLA
        # aliases input → output buffers (in-place ring-buffer writes, no
        # per-step cache copy)
        self._generate = jax.jit(generate_fn, donate_argnums=(5,))
        # continuous-batching primitives (ContinuousScheduler): jitted here so
        # every scheduler instance over this server shares the compiled
        # executables; the slot-pool state they donate lives in the scheduler.
        # A speculative server's ONE pool-lifetime segment executable IS the
        # spec variant — never both, so the single-_segment invariant holds
        # in either mode (SchedulerAudit.assert_single_segment)
        weights = (self.params, self._prequant)
        if serving.speculate:
            self._segment = _BoundWeights(segment_spec_fn, weights,
                                          donate_argnums=(3, 4, 5))
        else:
            self._segment = _BoundWeights(segment_fn, weights,
                                          donate_argnums=(1, 2, 3))
        self._admit = _BoundWeights(admit_fn, (params,),
                                    donate_argnums=(3, 4, 5))
        # paged continuous-batching primitives: same sharing story as above
        # (compiled once per server; the scheduler owns the donated pool)
        self._admit_paged = _BoundWeights(admit_paged_fn,
                                          (params, self._prequant),
                                          donate_argnums=(4, 5, 6))
        # shared-prefix admissions and chunked-prefill continuations share
        # the same continuation executable
        if not (self.prefix_sharing or self.chunk_tokens):
            self._admit_shared = None
        elif not self.masters_mode:
            self._admit_shared = _BoundWeights(admit_shared_pool_fn,
                                               (params,),
                                               donate_argnums=(7, 8, 9))
        else:
            # master-backed variant: prefix replayed from full-precision
            # registry masters — mandatory at int KV (the pool's int8 rows
            # were quantized on the *owner's* per-row grid and are not
            # bit-shareable), opt-in at kv16 via ``kv16_masters``
            self._admit_shared = _BoundWeights(_admit_shared_body, (params,),
                                               donate_argnums=(10, 11, 12))
        self._clear_rows = _BoundWeights(clear_rows_fn, (),
                                         donate_argnums=(1,))
        # preemption restore: a suspended row re-admits by replaying its own
        # processed tokens as the continuation prefix — always from the
        # host-side masters its eviction snapshotted (the row's blocks were
        # released to the pool), so the master-replay continuation body is
        # the restore executable at EVERY precision. At int KV that is
        # literally self._admit_shared (same jit object, zero extra
        # compiles); at kv16 the pool-gather shared wave cannot serve (there
        # are no blocks left to gather from), so the master body gets its
        # own jit — one more admission-side executable per server, while the
        # pool-lifetime single-_segment invariant is untouched.
        if serving.preemption and not (serving.paged_kv
                                       and T.supports_prefix_sharing(cfg)):
            raise ValueError(
                "preemption requires the paged KV pool on a full-causal "
                "attention stack (supports_prefix_sharing): suspended rows "
                "resume through the continuation-prefill executable")
        # built on every capable stack (not just under preemption): crash
        # recovery re-admits checkpointed rows through the exact same
        # executable, and jit objects compile lazily — an unused restore
        # path costs nothing
        if not (serving.paged_kv and T.supports_prefix_sharing(cfg)):
            self._admit_restore = None
        elif self.masters_mode and self._admit_shared is not None:
            self._admit_restore = self._admit_shared
        else:
            self._admit_restore = _BoundWeights(_admit_shared_body,
                                                (params,),
                                                donate_argnums=(10, 11, 12))

    def _scatter_blocks(self, pool, rows, dest, sidx, bt_rows=None):
        """Scatter dense admission rows into the paged pool (traced helper).

        ``rows`` is the stacked contiguous ``[L, a, slots_p, ...]`` cache an
        admission prefill produced; each row is cut into ``n_lblk`` blocks
        and written at physical ids ``dest [a, n_lblk]`` (out-of-range =
        skip: wave padding, unallocated tail, shared prefix blocks).
        ``bt_rows`` is the mapping installed in the block table — it differs
        from ``dest`` exactly when shared blocks are mapped-but-not-written.
        Per-row scales and the block table land at pool rows ``sidx``.
        """
        nlb, bs = self.n_lblk, self.block_size
        L = rows.k.shape[0]                # layers whose KV the pool holds
        a = dest.shape[0]

        def blk(x):
            return x.reshape(L, a, nlb, bs, *x.shape[3:])

        bt = pool.block_table.at[:, sidx].set(
            dest if bt_rows is None else bt_rows, mode="drop")
        return pool._replace(
            k=pool.k.at[:, dest].set(blk(rows.k), mode="drop"),
            v=pool.v.at[:, dest].set(blk(rows.v), mode="drop"),
            token_idx=pool.token_idx.at[:, dest].set(blk(rows.token_idx),
                                                     mode="drop"),
            k_scale=pool.k_scale.at[:, sidx].set(rows.k_scale, mode="drop"),
            v_scale=pool.v_scale.at[:, sidx].set(rows.v_scale, mode="drop"),
            block_table=bt)

    def _select_profile(self, critical: bool) -> int:
        if self.manager is None:
            return 0
        return self.manager.select(accuracy_critical=critical)

    def generate(self, prompts: np.ndarray, max_new: int,
                 accuracy_critical: bool = False, *,
                 row_budget: Optional[np.ndarray] = None,
                 prompt_len: Optional[np.ndarray] = None,
                 row_critical: Optional[np.ndarray] = None,
                 account_rows: Optional[int] = None) -> dict:
        """Batched greedy generation, fused: one prefill dispatch + one decode
        dispatch. prompts ``[B, S]`` int32 (ragged requests left-padded to a
        common length). ``prompt_len [B]`` marks each row's real length: rows
        then get per-row rope offsets, pad-key masks, logical-position KV
        handoff, and per-row ``pos0 = prompt_len`` — a mixed-length batch
        generates exactly what each row would solo. ``row_budget [B]`` masks
        per-row tokens at index ≥ budget to −1 (early stop for heterogeneous
        request budgets). With a manager, per-row data (``row_budget`` /
        ``row_critical``) switches the schedule to the exact ragged ledger
        (step ``i`` bills only rows still live); otherwise ``account_rows``
        rows are billed every step. Returns tokens + the per-step profile
        trace."""
        b, s = prompts.shape
        if self.manager is None:
            schedule = np.zeros((max_new,), np.int32)
        elif row_budget is not None or row_critical is not None:
            rb_plan = (np.full((b,), max_new) if row_budget is None
                       else np.minimum(np.asarray(row_budget), max_new))
            rc = (np.full((b,), bool(accuracy_critical))
                  if row_critical is None else np.asarray(row_critical, bool))
            schedule = self.manager.plan_schedule_ragged(max_new, rb_plan, rc)
        else:
            n_account = b if account_rows is None else account_rows
            schedule = self.manager.plan_schedule(max_new, n_account,
                                                  accuracy_critical=accuracy_critical)
        batch = {"tokens": jnp.asarray(prompts)}
        if prompt_len is not None:
            batch["prompt_len"] = jnp.asarray(prompt_len, jnp.int32)
        logits, caches = self._prefill(self.params, int(schedule[0]), batch)
        pos0 = (jnp.full((b,), s, jnp.int32) if prompt_len is None
                else jnp.asarray(prompt_len, jnp.int32))
        rb = (jnp.full((b,), max_new, jnp.int32) if row_budget is None
              else jnp.asarray(row_budget, jnp.int32))
        toks, pids, _ = self._generate(self.params, self._prequant,
                                       jnp.asarray(schedule),
                                       logits, pos0, caches, rb)
        # repro: allow(host-sync) the call's single decode sync, at the end
        toks = np.asarray(toks)
        # repro: allow(host-sync) profile trace decode, same single sync point
        trace = [self.engine.profile_names[p] for p in np.asarray(pids)]
        return {"tokens": [row.tolist() for row in toks],
                "profile_trace": trace}

    def generate_stepwise(self, prompts: np.ndarray, max_new: int,  # repro: allow(host-sync) seed oracle syncs per token by design
                          accuracy_critical: bool = False) -> dict:
        """Seed per-token host loop (one dispatch + host argmax per token).
        Kept as the fused path's oracle and the benchmark baseline."""
        b, s = prompts.shape
        pid = self._select_profile(accuracy_critical)
        logits, caches = self._prefill(self.params, pid,
                                       {"tokens": jnp.asarray(prompts)})
        if self.manager is not None:
            self.manager.account(pid, b)    # prefill billed like an inference
        out = [int(np.argmax(np.asarray(logits)[i])) for i in range(b)]
        tokens = [list(row) for row in prompts.tolist()]
        trace = [self.engine.profile_names[pid]]
        next_tok = jnp.asarray(np.asarray(out, np.int32)[:, None])
        for step in range(max_new - 1):
            pid = self._select_profile(accuracy_critical)
            pos = jnp.full((b,), s + step, jnp.int32)
            logits, caches = self._decode(self.params, pid, next_tok, pos, caches)
            if self.manager is not None:
                self.manager.account(pid, b)
            nxt = np.argmax(np.asarray(logits), axis=-1).astype(np.int32)
            for i in range(b):
                tokens[i].append(int(next_tok[i, 0]))
            next_tok = jnp.asarray(nxt[:, None])
            trace.append(self.engine.profile_names[pid])
        for i in range(b):
            tokens[i].append(int(next_tok[i, 0]))
        return {"tokens": [t[s:] for t in tokens], "profile_trace": trace}

    def serve(self, requests: Sequence[Request]) -> list[dict]:
        """Request batching: group by padded length up to ``max_batch``; one
        fused *ragged* generate call per group. Mixed-length requests are
        left-padded and ride in with per-row ``prompt_len`` (per-row rope
        offsets, pad-key masks, logical-position KV handoff, per-row decode
        start) so every row's tokens match a solo run. The batch is padded to
        ``max_batch`` (pad rows: budget 0, ``prompt_len`` 0 → fully masked) so
        every equal-length group reuses one compiled executable. MoE group
        sizes are bucketed to powers of two instead — pad rows are dropped
        from the capacity dispatch (``token_valid``), and the compile count
        stays logarithmic in ``max_batch`` rather than one executable per
        distinct group size. Each result's ``profile_trace`` is sliced to its
        own ``max_new``; the ledger bills per step only the rows still live."""
        results: list[dict] = [None] * len(requests)  # type: ignore
        order = sorted(range(len(requests)), key=lambda i: len(requests[i].tokens))
        for i0 in range(0, len(order), self.scfg.max_batch):
            group = order[i0:i0 + self.scfg.max_batch]
            maxlen = max(len(requests[i].tokens) for i in group)
            rows = (_next_pow2(max(2, len(group))) if self.cfg.family == "moe"
                    else self.scfg.max_batch)
            prompts = np.zeros((rows, maxlen), np.int32)
            budget = np.zeros((rows,), np.int32)
            plen = np.zeros((rows,), np.int32)       # pad rows: fully masked
            crit = np.zeros((rows,), bool)
            for row, i in enumerate(group):
                t = requests[i].tokens
                prompts[row, maxlen - len(t):] = t   # left-pad
                budget[row] = requests[i].max_new
                plen[row] = len(t)
                crit[row] = requests[i].accuracy_critical
            max_new = max(requests[i].max_new for i in group)
            out = self.generate(prompts, max_new, row_budget=budget,
                                prompt_len=plen, row_critical=crit)
            for row, i in enumerate(group):
                mn = requests[i].max_new
                results[i] = {"tokens": out["tokens"][row][:mn],
                              "profile_trace": out["profile_trace"][:mn]}
        return results
