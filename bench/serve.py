"""The system under test: build the server of a cell, warm it up, and drive
one measured window through ``ContinuousScheduler.submit`` / ``step``.

The server is built from the program's public pieces: ``paper_profiles``,
``AdaptiveEngine``, ``AdaptiveServer(..., manager=None)`` (profile 0,
A16-W8, pinned: a budget manager would change the arithmetic whenever the
speed changes) and ``ContinuousScheduler``. Host spans around the
generator, ``submit``, ``step`` and result polling are
``jax.profiler.TraceAnnotation`` s, so a traced run places them on the
device's clock.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.engine import AdaptiveEngine, QuantIndex
from repro.core.profiles import paper_profiles
from repro.models import transformer as T
from repro.runtime import compute_dtype
from repro.serving.engine import AdaptiveServer, Request, ServingConfig
from repro.serving.scheduler import ContinuousScheduler

from . import weights as W
from .load import Traffic

SPAN = jax.profiler.TraceAnnotation


def model_config(conf: dict, arch):
    """The registry's ModelConfig with the file's overrides, checked by the
    configuration's architecture module against the file's sizes (the file
    says how the model is run)."""
    cfg = dataclasses.replace(get_config(conf["registry"]),
                              **conf.get("overrides", {}))
    arch.check_config(cfg, conf["model"])
    return cfg


def build(cfg, conf: dict, cell: dict, seed: int, arch):
    """(server, params): the architecture's weights from the seed, the
    pinned profile first."""
    params = arch.make_weights(conf["model"], seed, compute_dtype())
    expected = jax.eval_shape(
        lambda k: jax.tree_util.tree_map_with_path(
            lambda p, a: a.astype(compute_dtype()) if p[-1].key == "w" else a,
            T.init_params(cfg, k)), jax.random.PRNGKey(0))
    W.check_layout(params, expected)
    names = T.quant_layer_names(cfg)
    profs = paper_profiles(names, inner_layers=[])
    if profs[0].name != conf["profile"]:
        raise ValueError(f"profile 0 is {profs[0].name}, the configuration "
                         f"pins {conf['profile']}")
    engine = AdaptiveEngine(tuple(profs), QuantIndex(names), None)
    sv = cell["serving"]
    scfg = ServingConfig(slots=sv["slots"], kv_bits=conf["kv_bits"],
                         max_batch=sv["max_batch"],
                         pool_blocks=sv["pool_blocks"],
                         block_size=sv.get("block_size", 16),
                         prefix_cache=sv.get("prefix_cache", True),
                         paged_backend="auto")
    return AdaptiveServer(cfg, params, engine, scfg, manager=None), params


def next_pow2(n: int) -> int:
    return 1 << (int(n) - 1).bit_length()


def warm_up(srv, sched: ContinuousScheduler, traffic: Traffic) -> int:
    """Run every executable the window can reach once, at each shape this
    cell's traffic can give it: the admission wave at each power-of-two row
    count up to the pool and each prompt bucket of the mix, and the decode
    segment. Each call drops all of its writes (slot and block indices out
    of range), so the pool is left as it was. Returns the calls made."""
    n = 0
    mb = sched.n_slots
    nb = sched.allocator.n_blocks
    rows = [1 << i for i in range(next_pow2(mb).bit_length())]
    for a in rows:
        for bucket in traffic.prompt_buckets(sched.bucket_min):
            batch = {"tokens": jnp.asarray(np.ones((a, bucket), np.int32)),
                     "prompt_len": jnp.asarray(np.full((a,), bucket,
                                                       np.int32))}
            tok0, _, sched._tok, sched._pos, sched._caches = \
                srv._admit_paged(0, batch,
                                 jnp.asarray(np.full((a,), mb, np.int32)),
                                 jnp.asarray(np.full((a, sched.n_lblk), nb,
                                                     np.int32)),
                                 sched._tok, sched._pos, sched._caches)
            np.asarray(tok0)
            n += 1
    toks, ok, sched._tok, sched._pos, sched._caches = srv._segment(
        jnp.asarray(np.zeros((sched.quantum,), np.int32)), sched._tok,
        sched._pos, sched._caches, jnp.asarray(sched.remaining, jnp.int32),
        jnp.asarray(np.full((mb,), -1, np.int32)))
    np.asarray(toks)
    return n + 1


@dataclasses.dataclass
class Req:
    submit: float       # s from window start: when submit() returned
    plen: int
    max_new: int
    prompt: np.ndarray
    n: int = 0               # tokens visible to the client
    tokens: list = dataclasses.field(default_factory=list)
    status: str = ""


class Window:
    """One measured window: its requests, steps, segments and admissions
    are what the metrics read."""

    def __init__(self, sched: ContinuousScheduler, traffic: Traffic,
                 queue_rows: int = 2):
        self.sched = sched
        self.traffic = traffic
        self.queue_rows = queue_rows
        self.reqs: dict[int, Req] = {}
        self.steps: list[tuple[float, float]] = []     # (start, end) s
        self.segments: list[tuple[int, list]] = []     # (step, [(ctx0, n)])
        self.admitted: list[tuple[int, int]] = []      # (step, prompt tokens)
        self.submit_s: list[float] = []                # time in submit(), s
        self.failed_submit = 0
        self._live: set[int] = set()
        self._seen_seg = None
        self._n_admit_seen = len(sched.admission_log)

    def _submit(self, prompt, max_new):
        t0 = time.perf_counter()
        with SPAN("bench.submit"):
            try:
                rid = self.sched.submit(Request(tokens=prompt,
                                                max_new=max_new))
            except ValueError:
                self.failed_submit += 1
                return
        t1 = time.perf_counter()
        self.reqs[rid] = Req(t1 - self.t0, len(prompt), max_new, prompt)
        self._live.add(rid)
        self.submit_s.append(t1 - t0)

    def _offer(self) -> None:
        """Top the queue up to ``queue_rows`` pools of waiting requests."""
        with SPAN("bench.generate"):
            want = self.queue_rows * self.sched.n_slots
            while self.sched.pending < want:
                self._submit(*next(self.traffic))

    def _observe(self, k: int) -> None:
        sched = self.sched
        log = sched.admission_log
        for rid in log[self._n_admit_seen:]:
            if rid in self.reqs:
                self.admitted.append((k, self.reqs[rid].plen))
        self._n_admit_seen = len(log)
        inflight = sched._inflight
        if inflight and inflight[-1]["kind"] == "seg" \
                and inflight[-1] is not self._seen_seg:
            e = inflight[-1]
            self._seen_seg = e
            rows = []
            for slot, rid, n in e["rows"]:
                r = self.reqs.get(rid)
                if r is None or n <= 0:
                    continue
                rem_before = int(sched.remaining[slot]) + n
                rows.append((r.plen + r.max_new - rem_before, n))
            self.segments.append((k, rows))
        with SPAN("bench.poll"):
            done = dict(sched.poll_completed())
            for rid in list(self._live):
                r = self.reqs[rid]
                res = done.get(rid) or sched.results.get(rid)
                if res is None:
                    continue
                r.n = max(r.n, len(res["tokens"]))
                if rid in done:
                    r.tokens = list(res["tokens"])
                    r.status = str(getattr(res["status"], "value",
                                           res["status"]))
                    self._live.discard(rid)

    def run(self, seconds: float, trace_at=None) -> None:
        """Drive the scheduler for ``seconds``. ``trace_at(now)`` is called
        between steps (the traced run starts and stops the profiler
        there)."""
        self.t0 = time.perf_counter()
        k = 0
        with SPAN("bench.window"):
            while True:
                now = time.perf_counter() - self.t0
                if now >= seconds:
                    break
                if trace_at is not None:
                    trace_at(self, now)
                self._offer()
                t_start = time.perf_counter() - self.t0
                with SPAN("bench.step"):
                    self.sched.step()
                t_end = time.perf_counter() - self.t0
                self.steps.append((t_start, t_end))
                self._observe(k)
                k += 1
        self.t_end = time.perf_counter() - self.t0
