"""Async serving runtime: continuous batching over the plan cache.

One :class:`AsyncServingRuntime` owns

  * a **bucketed planned prefill** per power-of-two prompt bucket, fetched
    through the content-hashed plan cache (warm buckets never re-plan) and
    jitted once per plan_id;
  * a fixed-width **batched decode step** (``decode_step_batched`` jitted at
    ``max_batch``) whose slots requests join/leave at token boundaries;
  * a :class:`~repro.serving.kv_pool.PagedKVPool` seeded **directly from the
    planned prefill's per-layer K/V outputs** (``mode="prefill_kv"``) —
    no decode replay of the prompt — with a replay fallback for families
    whose decode state is not pure attention K/V (mamba/rwkv hybrids);
  * an asyncio event loop that interleaves admission, planned prefill of
    incoming requests, and decode of in-flight ones at token boundaries
    (continuous batching; JAX's async dispatch pipelines the prefill and
    decode computations it enqueues).

The runtime never re-plans a warm bucket: each request's prefill goes
through ``plan_and_compile`` against the shared plan cache, so steady-state
traffic is 100 % cache hits (asserted by ``benchmarks/serving_throughput``).
"""
from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.executor import plan_and_compile
from ..core.faults import FaultInjectedError
from ..core import tracing
from ..core.ir import SystemCatalog
from ..core.ledger import FlightRecorder, MemoryLedger, default_ledger
from ..core.mqo import SubplanCache, mqo_run, subdag_keys
from ..core.resilience import classify
from ..core.plan_cache import (PlanCache, default_plan_cache,
                               load_plan_cache, save_plan_cache)
from ..models.decode import decode_step, decode_step_batched, init_cache
from ..models.lm import CATALOG, LM
from .admission import AdmissionController, bucket_len
from .kv_pool import PagedKVPool
from .metrics import MetricsRegistry, RequestMetrics, ServingMetrics
from .scheduler import ContinuousBatchScheduler, TenantScheduler


@dataclass(frozen=True)
class ServeRequest:
    rid: object
    prompt: tuple                    # token ids
    gen: int
    arrival: float = 0.0             # seconds after run() start
    deadline_s: Optional[float] = None   # budget from arrival; None = none

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)


@dataclass
class ServeResult:
    rid: object
    tokens: list = field(default_factory=list)
    # ok | rejected | truncated | deadline_exceeded | error | timeout
    status: str = "ok"
    metrics: Optional[RequestMetrics] = None
    error: Optional[dict] = None     # structured failure detail (non-ok)

    @property
    def ok(self) -> bool:
        return self.status in ("ok", "truncated")


@dataclass
class AnalysisRequest:
    """One analytical query submitted to the multi-query admission loop.

    ``batch_param`` names an input whose value may differ across otherwise
    identical queries (a PageRank seed set, a top-k query vector): requests
    sharing a plan fingerprint modulo that slot are coalesced per admission
    tick into one vmapped planned forward.  ``store_versions`` are the
    (name, version) pairs of the bound stores — they key the sub-DAG cache
    entries so appends provably invalidate."""

    rid: object
    planned: object                  # PlannedFunction
    inputs: dict
    params: object = None
    tenant: object = "default"
    batch_param: Optional[str] = None
    store_versions: tuple = ()
    tied_to: object = None           # ledger owner of the producing store
    aux: Optional[dict] = None


@dataclass
class AnalysisResult:
    rid: object
    value: object = None
    status: str = "ok"               # ok | error
    error: Optional[dict] = None
    shared_hits: int = 0             # cached sub-DAGs reused by this query
    executed: int = 0                # residual nodes actually run
    deduped: bool = False            # rode an identical in-flight query
    batched: bool = False            # ran inside a vmapped batch
    ttfr_ms: float = 0.0             # submit -> first result
    # time.perf_counter() stamps: run_analyses took the request in; the
    # execution that produced the value (its own, its vmapped batch's, or
    # the one its twin leader ran) started and finished; the result
    # settled into its call's results.  None where nothing ran.
    enqueued_at: Optional[float] = None
    started_at: Optional[float] = None
    finished_at: Optional[float] = None
    settled_at: Optional[float] = None

    @property
    def ok(self) -> bool:
        return self.status == "ok"


class AsyncServingRuntime:
    def __init__(self, model: LM, params, *, max_batch: int = 4,
                 max_seq: int = 128, page_size: int = 16,
                 page_budget: int | None = None,
                 bucket_lo: int = 8, engines=("xla",),
                 syscat: Optional[SystemCatalog] = None,
                 plan_cache: Optional[PlanCache] = None,
                 plan_cache_dir: Optional[str] = None,
                 admission: Optional[AdmissionController] = None,
                 use_prefill_kv: Optional[bool] = None,
                 registry: Optional[MetricsRegistry] = None,
                 ledger: Optional[MemoryLedger] = None,
                 recorder: Optional[FlightRecorder] = None,
                 snapshot_every: int = 64,
                 faults=None,
                 degrade=None,
                 prefill_retries: int = 2,
                 decode_fault_cap: int = 8,
                 subplan_cache: Optional[SubplanCache] = None,
                 subplan_budget: Optional[int] = None,
                 tenant_weights: Optional[dict] = None,
                 analysis_tick: int = 16,
                 prefill_batch: int = 4):
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.bucket_lo = bucket_lo
        self.engines = tuple(engines)
        self.syscat = syscat or SystemCatalog()
        self.pc = plan_cache if plan_cache is not None else \
            default_plan_cache()
        self.plan_cache_dir = plan_cache_dir
        if plan_cache_dir:
            load_plan_cache(plan_cache_dir, self.pc)   # warm start
        self.kv_mode = model.supports_prefill_kv() if use_prefill_kv is None \
            else bool(use_prefill_kv)
        # one registry for both workload families: LM request series land
        # as "lm.*" summaries, analytical runs (run_analysis) as
        # "analytics.*" — a shared registry makes one report() cover both
        self.registry = registry if registry is not None else \
            MetricsRegistry()
        # resource accounting + incident capture: the ledger tracks every
        # resident pytree (KV pool, plan-cache entries, store payloads);
        # the flight recorder keeps a bounded ring of recent run traces +
        # telemetry snapshots, dumped on rejection / overflow / error
        self.ledger = ledger if ledger is not None else \
            getattr(self.pc, "ledger", None) or default_ledger()
        self.recorder = recorder if recorder is not None else FlightRecorder()
        self.snapshot_every = max(int(snapshot_every), 1)
        self.pool = PagedKVPool(model, max_batch, max_seq,
                                page_size=page_size, page_budget=page_budget,
                                registry=self.registry, ledger=self.ledger)
        self.scheduler = ContinuousBatchScheduler(max_batch)
        self.admission = admission or AdmissionController()
        self.metrics = ServingMetrics(registry=self.registry)
        self._prefill_fns: dict = {}     # bucket -> (PlannedFunction, jitted)
        self._jitted_by_plan: dict = {}  # plan_id -> jitted callable
        # the pool cache is donated (argnums 1): on backends with donation
        # the per-tick cache update aliases the preallocated pool instead of
        # copying it; every call site rebinds pool.cache to the result
        self._dstep = jax.jit(lambda p, c, t, i: decode_step_batched(
            self.model, p, c, t, i), donate_argnums=1)
        self._dstep1 = jax.jit(lambda p, c, t, i: decode_step(
            self.model, p, c, t, i), donate_argnums=1)
        self._results: dict = {}
        self._t0 = time.perf_counter()
        # fault tolerance: an optional FaultInjector exercises the
        # admission/prefill/decode seams; prefill faults retry by
        # re-enqueueing (bounded), decode-tick faults retry the whole tick
        # (state is untouched — the fault fires before the donated decode
        # call); a DegradePolicy (serving.degrade) cheapens analytical
        # plans under overload
        self.faults = faults
        self.degrade = degrade
        self.prefill_retries = int(prefill_retries)
        self.decode_fault_cap = int(decode_fault_cap)
        self._prefill_attempts: dict = {}   # rid -> failed attempts
        self._tick_no = 0
        self._decode_faults = 0             # consecutive faulted ticks
        # multi-query analytics: a byte-budgeted cache of materialized
        # sub-DAG intermediates (cross-query CSE), a weighted round-robin
        # tenant scheduler feeding the admission loop, and single-flight
        # futures so concurrent identical sub-DAGs compute once
        if subplan_cache is not None:
            self.subplans: Optional[SubplanCache] = subplan_cache
        elif subplan_budget is not None:
            self.subplans = SubplanCache(
                subplan_budget, ledger=self.ledger, recorder=self.recorder,
                registry=self.registry)
        else:
            self.subplans = None
        self.analysis_sched = TenantScheduler(tenant_weights)
        self.analysis_tick = max(int(analysis_tick), 1)
        self._analysis_inflight: dict = {}  # root key -> asyncio.Future
        # id(request) -> (results dict, t0) of the run_analyses call that
        # owns it: concurrent calls share one tenant scheduler, so a tick
        # may drain another call's request — settlement routes through the
        # owning call's sink, never the draining call's
        self._analysis_sinks: dict = {}
        # batched prefill: up to ``prefill_batch`` same-bucket waiting
        # requests prefill as ONE vmapped planned forward (1 disables);
        # deterministic fault replay needs per-request prefill sites, so
        # injection forces the sequential path
        self.prefill_batch = 1 if faults is not None \
            else max(int(prefill_batch), 1)
        self._prefill_base: dict = {}    # plan_id -> unjitted prefill call
        self._vjitted_by_plan: dict = {}  # plan_id -> jit(vmap(prefill))

    # -- planning ----------------------------------------------------------
    def _now(self) -> float:
        return time.perf_counter() - self._t0

    def bucket_of(self, prompt_len: int) -> int:
        return bucket_len(prompt_len, lo=self.bucket_lo, hi=self.max_seq)

    def is_warm(self, bucket: int) -> bool:
        return bucket in self._prefill_fns

    def _plan_prefill(self, bucket: int):
        """Fetch (or plan, on a cold bucket) the bucket's prefill through the
        plan cache; jit once per plan_id.  The jitted function also extracts
        the first generated token at a *traced* prompt length, so serving
        never triggers a per-request recompile."""
        mode = "prefill_kv" if self.kv_mode else "prefill"
        t0 = time.perf_counter()
        hits0 = self.pc.hits
        plan = self.model.build_plan(1, bucket, mode=mode)
        fwd = plan_and_compile(plan, CATALOG, self.syscat,
                               engines=self.engines, cache=self.pc)
        self.metrics.observe_plan(hit=self.pc.hits > hits0)
        jitted = self._jitted_by_plan.get(fwd.plan_id)
        if jitted is None:
            vocab = self.cfg.vocab

            def _prefill_call(p, toks, n, _f=fwd):
                outs = _f(p, {"tokens": toks})
                logits = outs[0] if isinstance(outs, tuple) else outs
                row = jax.lax.dynamic_index_in_dim(logits, n - 1, axis=1,
                                                   keepdims=False)
                return outs, jnp.argmax(row[0, :vocab]).astype(jnp.int32)

            jitted = jax.jit(_prefill_call)
            self._jitted_by_plan[fwd.plan_id] = jitted
            self._prefill_base[fwd.plan_id] = _prefill_call
            # tie the jitted wrapper's lifetime to its plan-cache entry:
            # _jitted_by_plan never evicts, so once byte-budget eviction
            # drops the entry this registration shows up in ledger.leaks()
            # as "evicted" — a real retained-executable leak signal
            self.ledger.register(
                ("plan_jit", fwd.plan_id), nbytes=0, kind="plan_jit",
                tied_to=("plan_cache", fwd.plan_id))
        self._prefill_fns[bucket] = (fwd, jitted)
        return fwd, jitted, (time.perf_counter() - t0) * 1e3

    def _vjit_prefill(self, plan_id):
        """jit(vmap) of a bucket's prefill forward, cached per plan and
        ledger-tied to the plan-cache entry (same leak signal as the
        unbatched wrapper)."""
        vj = self._vjitted_by_plan.get(plan_id)
        if vj is None:
            base = self._prefill_base[plan_id]
            vj = jax.jit(jax.vmap(base, in_axes=(None, 0, 0)))
            self._vjitted_by_plan[plan_id] = vj
            self.ledger.register(
                ("plan_jit_batched", plan_id), nbytes=0,
                kind="plan_jit", tied_to=("plan_cache", plan_id))
        return vj

    def warmup(self, prompt_lens: Sequence[int]) -> None:
        """Plan + compile every bucket the trace will touch (prefill *and*
        its pool-seed program), and trace the batched decode step, so
        serving-time work is pure execution."""
        for n in sorted({self.bucket_of(n) for n in prompt_lens}):
            fwd, jitted, _ = self._plan_prefill(n)
            outs, _ = jitted(self.params, jnp.zeros((1, n), jnp.int32),
                             jnp.int32(n))
            if self.kv_mode and self.pool.alloc("__warmup__", 1) is not None:
                # compiling the bucket's seed program writes zero-token K/V
                # into a scratch slot; harmless — any join overwrites it
                self.pool.seed("__warmup__", outs[1:], n)
                self.pool.free("__warmup__")
            if self.kv_mode and self.prefill_batch > 1:
                # the batched-prefill forward too: serve-time batches pad to
                # ONE fixed width per bucket, so this is the only vmapped
                # shape the bucket ever compiles — and warm the per-row KV
                # slice + seed, which compile their own eager kernels
                w = min(self.prefill_batch, self.max_batch)
                outs_b, _ = self._vjit_prefill(fwd.plan_id)(
                    self.params, jnp.zeros((w, 1, n), jnp.int32),
                    jnp.full((w,), n, jnp.int32))
                kv0 = jax.tree.map(lambda x: x[0], outs_b[1:])
                if self.pool.alloc("__warmup__", 1) is not None:
                    self.pool.seed("__warmup__", kv0, n)
                    self.pool.free("__warmup__")
        toks = jnp.zeros((self.max_batch, 1), jnp.int32)
        idxs = jnp.zeros((self.max_batch,), jnp.int32)
        # keep the returned cache: the input buffers were donated, and the
        # position-0 write of token 0 is overwritten by any join
        _, self.pool.cache = self._dstep(self.params, self.pool.cache,
                                         toks, idxs)
        if not self.kv_mode:
            # trace the replay-fallback step too, so the first real
            # request's TTFT is execution, not compilation
            self._dstep1(self.params, init_cache(self.model, 1, self.max_seq),
                         toks[:1], jnp.int32(0))

    # -- telemetry ----------------------------------------------------------
    def telemetry_snapshot(self) -> dict:
        """One continuous-telemetry record: ledger totals, KV occupancy +
        fragmentation, per-bucket queue depth, plan-cache hit/byte ratios,
        decode-batch occupancy.  Published as registry gauges and recorded
        in the flight recorder ring."""
        pc_stats = self.pc.stats()
        snap = {
            "ledger": self.ledger.snapshot(),
            "kv": {**self.pool.occupancy(), **self.pool.fragmentation()},
            "queues": {b: len(q) for b, q in self.scheduler.queues.items()
                       if q},
            "queue_depth": self.scheduler.queue_depth(),
            "active_slots": self.scheduler.n_active(),
            "plan_cache": pc_stats,
            "ticks": self.metrics.ticks,
        }
        g = self.registry.gauge
        g("ledger.total_bytes").set(snap["ledger"]["total_bytes"])
        g("ledger.peak_bytes").set(snap["ledger"]["peak_bytes"])
        g("plan_cache.hit_rate").set(pc_stats["hit_rate"])
        g("plan_cache.bytes").set(pc_stats["bytes"])
        g("serving.queue_depth").set(snap["queue_depth"])
        g("serving.active_slots").set(snap["active_slots"])
        return snap

    def _maybe_snapshot(self, force: bool = False) -> None:
        if force or self.metrics.ticks % self.snapshot_every == 0:
            self.recorder.record("telemetry", self.telemetry_snapshot())

    # -- admission ----------------------------------------------------------
    def _reject(self, req: ServeRequest, reason: str) -> None:
        self.metrics.rejected += 1
        self._results[req.rid] = ServeResult(
            req.rid, [], "rejected", None,
            error={"reason": reason, "rid": str(req.rid)})
        self.recorder.trip("admission_reject", {
            "rid": str(req.rid), "reason": reason,
            "prompt_len": req.prompt_len, "gen": req.gen,
            "queue_depth": self.scheduler.queue_depth(),
            "active": self.scheduler.n_active()})

    def _deadline_at(self, req: ServeRequest) -> float:
        """Absolute (run-clock) expiry; +inf when no deadline is set."""
        if req.deadline_s is None:
            return float("inf")
        return req.arrival + req.deadline_s

    def _estimate_completion_s(self, req: ServeRequest) -> Optional[float]:
        """Observed-latency completion estimate for deadline admission:
        queue wait + TTFT + gen * TPOT from the lm.* summaries.  None until
        enough traffic has been observed to estimate at all."""
        s = self.metrics
        if s._ttft.count < 1 or (req.gen > 1 and s._tpot.count < 1):
            return None
        return (s._queue_wait.mean + s._ttft.mean
                + max(req.gen - 1, 0) * s._tpot.mean)

    def submit(self, req: ServeRequest) -> None:
        if self.faults is not None:
            # admission stall: the front door pauses (queue growth +
            # deadline pressure); stall sites never raise
            self.faults.check(("admission", str(req.rid)))
        if req.prompt_len < 1 or req.gen < 1:
            self._reject(req, "empty prompt or zero gen")
            return
        if req.prompt_len + req.gen > self.max_seq:
            self._reject(req, "exceeds max_seq")
            return
        try:
            bucket = self.bucket_of(req.prompt_len)
        except ValueError:
            self._reject(req, "unbucketable")
            return
        if req.deadline_s is not None:
            now = self._now()
            if now >= self._deadline_at(req):
                self._resolve_deadline(req, phase="submit")
                return
            est = self._estimate_completion_s(req)
            if est is not None and now + est > self._deadline_at(req):
                # cannot finish in time at observed latencies: shedding at
                # the door beats burning KV pages on a doomed request
                self._reject(req, "deadline_unmeetable")
                return
        action = self.admission.decide(
            warm=self.is_warm(bucket),
            queue_depth=self.scheduler.queue_depth(),
            active=self.scheduler.n_active(), max_batch=self.max_batch)
        if action == "reject":
            self._reject(req, "queue full")
            return
        # "admit" and "queue" both enqueue; a cold bucket's head is only
        # *planned* once the decode batch drains (scheduler-side gate)
        self.scheduler.enqueue(req, bucket, self._now())

    # -- deadlines -----------------------------------------------------------
    def _resolve_deadline(self, req: ServeRequest, *, phase: str,
                          tokens: Sequence[int] = (), rm=None) -> None:
        """Resolve a request whose deadline expired: structured error,
        partial tokens preserved, one deadline_miss trip per request."""
        self.metrics.registry.count("serving.deadline_miss")
        self._results[req.rid] = ServeResult(
            req.rid, list(tokens), "deadline_exceeded", rm,
            error={"reason": "deadline_exceeded", "rid": str(req.rid),
                   "phase": phase, "deadline_s": req.deadline_s,
                   "tokens_done": len(tokens)})
        self.recorder.trip("deadline_miss", {
            "rid": str(req.rid), "phase": phase,
            "deadline_s": req.deadline_s, "now": self._now(),
            "tokens_done": len(tokens)})

    def _expire_deadlines(self) -> None:
        """Deadline sweep, run once per loop iteration: queued requests are
        dropped in place; active ones leave at this token boundary, their
        KV pages going straight back to the pool (ledger-verified — the
        pool's one allocation never leaks per-request state)."""
        now = self._now()
        for w in self.scheduler.waiting():
            if now >= self._deadline_at(w.request):
                self.scheduler.remove(w)
                self._resolve_deadline(w.request, phase="queued")
        for st in list(self.scheduler.active()):
            if now >= self._deadline_at(st.request):
                self.scheduler.leave(st.slot)
                self.pool.free(st.request.rid)
                st.rm.finished_at = now
                self._resolve_deadline(st.request, phase="decode",
                                       tokens=st.out, rm=st.rm)

    # -- prefill + join ------------------------------------------------------
    def _prefill_and_join(self, req: ServeRequest, bucket: int,
                          enqueued_at: float) -> None:
        rm = RequestMetrics(req.rid, bucket=bucket,
                            prompt_len=req.prompt_len, gen=req.gen,
                            submitted_at=enqueued_at)
        if self.faults is not None:
            # before any allocation: a prefill fault leaves nothing behind
            self.faults.check(("prefill", str(req.rid), bucket))
        fwd, jitted, plan_ms = self._plan_prefill(bucket)
        rm.plan_ms = plan_ms
        t0 = time.perf_counter()
        rm.prefill_started_at = t0 - self._t0
        rm.prefill_width = rm.prefill_members = 1
        with tracing.span("lm.prefill", rid=req.rid, bucket=bucket,
                          members=1, width=1):
            padded_np = np.zeros((1, bucket), np.int32)
            padded_np[0, :req.prompt_len] = req.prompt
            padded = jnp.asarray(padded_np)
            outs, first_dev = jitted(self.params, padded,
                                     jnp.int32(req.prompt_len))
            # reserve prompt + the first decode write (position prompt_len
            # is written by the first tick, before extend() is consulted)
            self.pool.alloc(req.rid, req.prompt_len + 1)
            if self.kv_mode:
                self.pool.seed(req.rid, outs[1:], req.prompt_len)
            else:
                # replay fallback: families with recurrent state
                # (mamba/rwkv) rebuild the prompt state through the cached
                # decode path
                c1 = init_cache(self.model, 1, self.max_seq)
                for t in range(req.prompt_len):
                    _, c1 = self._dstep1(self.params, c1,
                                         jnp.asarray(padded_np[:, t:t + 1]),
                                         jnp.int32(t))
                self.pool.adopt(req.rid, c1)
            first = int(first_dev)
        rm.prefill_ms = (time.perf_counter() - t0) * 1e3
        now = self._now()
        rm.joined_at = rm.first_token_at = now
        st = self.scheduler.join(req, pos=req.prompt_len, tok=first,
                                 first_out=first, now=now)
        st.rm = rm
        self.metrics.joins += 1
        if st.done:                          # gen == 1: prefill was enough
            self._finish(st, "ok")

    def _pop_prefill_batch(self, w) -> list:
        """Starting from the chosen head ``w``, pop up to ``prefill_batch``
        same-bucket waiting requests that the decode batch and KV pool can
        conservatively absorb together.  Returns [(req, enqueued_at), ...]."""
        batch = [(self.scheduler.pop(w), w.enqueued_at)]
        if not self.kv_mode or self.prefill_batch <= 1:
            return batch
        q = self.scheduler.queues.get(w.bucket)
        pending_pages = self.pool.pages_for(batch[0][0].prompt_len + 1)
        while (q and len(batch) < self.prefill_batch
               and self.scheduler.n_active() + len(batch)
               < self.scheduler.max_batch
               and len(self.pool._free_slots) > len(batch)):
            nxt = q[0]
            need = self.pool.pages_for(nxt.request.prompt_len + 1)
            if self.pool.pages_in_use + pending_pages + need > \
                    self.pool.page_budget:
                break
            batch.append((self.scheduler.pop(nxt), nxt.enqueued_at))
            pending_pages += need
        return batch

    def _try_join(self) -> bool:
        """Fill free decode slots from the wait queues: FIFO within bucket,
        longest-waiting-first across buckets; cold buckets only when the
        batch has drained enough to afford planning.  When several
        same-bucket requests are waiting, they prefill as ONE vmapped
        planned forward (satellite of the multi-query work: identical token
        streams, one dispatch)."""
        joined = False
        while self.scheduler.free_slot() is not None:
            warm = {b for b in self.scheduler.queues if self.is_warm(b)}
            w = self.scheduler.peek_next(warm_buckets=warm)
            if w is None and self.admission.can_plan_cold(
                    active=self.scheduler.n_active(),
                    max_batch=self.max_batch):
                w = self.scheduler.peek_next()
            if w is None:
                break
            if not self.pool.can_admit(w.request.prompt_len + 1):
                break                        # memory pressure: keep queueing
            bucket = w.bucket
            batch = self._pop_prefill_batch(w)
            if len(batch) == 1:
                req, enq = batch[0]
                try:
                    self._prefill_and_join(req, bucket, enq)
                except Exception as exc:
                    self._prefill_failure(req, bucket, enq, exc)
            else:
                self._prefill_and_join_many(batch, bucket)
            joined = True
        return joined

    def _prefill_and_join_many(self, batch: list, bucket: int) -> None:
        """Prefill a same-bucket group as one jitted vmapped forward and
        join each member; falls back to the sequential per-request path if
        the batched call fails (nothing was allocated yet)."""
        try:
            # one plan fetch per member: the batch serves N requests, and
            # each keeps its own plan-cache hit + plan_ms accounting (warm
            # fetches are cache lookups, not re-planning)
            plan_mss = []
            for _ in batch:
                fwd, _, plan_ms = self._plan_prefill(bucket)
                plan_mss.append(plan_ms)
            vj = self._vjit_prefill(fwd.plan_id)
            # pad to the bucket's one warmed width: a short batch wastes a
            # few pad rows but never triggers a serve-time recompile
            width = max(min(self.prefill_batch, self.max_batch), len(batch))
            toks = np.zeros((width, 1, bucket), np.int32)
            ns = np.ones((width,), np.int32)
            for i, (req, _) in enumerate(batch):
                toks[i, 0, :req.prompt_len] = req.prompt
                ns[i] = req.prompt_len
            t0 = time.perf_counter()
            with tracing.span("lm.prefill",
                              rids=" ".join(str(r.rid) for r, _ in batch),
                              bucket=bucket, members=len(batch),
                              width=width):
                outs, firsts = vj(self.params, jnp.asarray(toks),
                                  jnp.asarray(ns))
                firsts = np.asarray(firsts)
            # the call's whole latency: every member waited for all of it
            prefill_ms = (time.perf_counter() - t0) * 1e3
            self.registry.count("lm.batched_prefills", len(batch))
        except Exception:
            for req, enq in batch:           # degrade to per-request prefill
                try:
                    self._prefill_and_join(req, bucket, enq)
                except Exception as exc:
                    self._prefill_failure(req, bucket, enq, exc)
            return
        for i, (req, enq) in enumerate(batch):
            rm = RequestMetrics(req.rid, bucket=bucket,
                                prompt_len=req.prompt_len, gen=req.gen,
                                submitted_at=enq)
            rm.plan_ms = plan_mss[i]
            rm.prefill_ms = prefill_ms
            rm.prefill_started_at = t0 - self._t0
            rm.prefill_width, rm.prefill_members = width, len(batch)
            self.pool.alloc(req.rid, req.prompt_len + 1)
            kv_i = jax.tree.map(lambda x, _i=i: x[_i], outs[1:])
            self.pool.seed(req.rid, kv_i, req.prompt_len)
            first = int(firsts[i])
            now = self._now()
            rm.joined_at = rm.first_token_at = now
            st = self.scheduler.join(req, pos=req.prompt_len, tok=first,
                                     first_out=first, now=now)
            st.rm = rm
            self.metrics.joins += 1
            if st.done:
                self._finish(st, "ok")

    def _prefill_failure(self, req: ServeRequest, bucket: int,
                         enqueued_at: float, exc: Exception) -> None:
        """A prefill attempt died (injected or real).  Clean up any pages
        the attempt claimed, then either re-enqueue (bounded retries,
        retryable errors only) or resolve with a structured error."""
        if self.pool.holds(req.rid):
            self.pool.free(req.rid)
        err = classify(exc, plan_id=f"prefill_bucket_{bucket}")
        attempts = self._prefill_attempts.get(req.rid, 0) + 1
        self._prefill_attempts[req.rid] = attempts
        self.metrics.registry.count("serving.prefill_faults")
        self.recorder.record("prefill_fault", {
            "rid": str(req.rid), "bucket": bucket, "attempt": attempts,
            "error": err.to_dict()})
        if err.retryable and attempts <= self.prefill_retries:
            # back of its bucket queue: the retry is a fresh occurrence of
            # the fault site, so rate-injected faults clear on replay
            self.scheduler.enqueue(req, bucket, enqueued_at)
            return
        self._prefill_attempts.pop(req.rid, None)
        self._results[req.rid] = ServeResult(
            req.rid, [], "error", None,
            error={"reason": "prefill_failed", "rid": str(req.rid),
                   "attempts": attempts, **err.to_dict()})
        self.recorder.trip("prefill_error", {
            "rid": str(req.rid), "bucket": bucket, "attempts": attempts,
            "error": err.to_dict()})

    # -- decode -------------------------------------------------------------
    def _finish(self, st, status: str, error: Optional[dict] = None) -> None:
        self.scheduler.leave(st.slot)
        self.pool.free(st.request.rid)
        self._prefill_attempts.pop(st.request.rid, None)
        st.rm.finished_at = self._now()
        self.metrics.finish(st.rm)
        self._results[st.request.rid] = ServeResult(
            st.request.rid, list(st.out), status, st.rm, error=error)

    def _decode_tick(self) -> bool:
        """One continuous-batching step: every active slot decodes one token
        at its own position; finished requests leave at this boundary."""
        active = self.scheduler.active()
        self.metrics.observe_tick(self.scheduler.queue_depth(),
                                  self.pool.occupancy()["fill"])
        self._maybe_snapshot()
        if not active:
            return False
        self._tick_no += 1
        if self.faults is not None:
            # the fault fires BEFORE the donated decode call, so a faulted
            # tick leaves the pool cache and every slot position untouched
            # — the retry is simply the next loop iteration re-running the
            # identical tick
            try:
                self.faults.check(("decode", self._tick_no))
            except FaultInjectedError as exc:
                self._decode_faults += 1
                self.metrics.registry.count("serving.decode_faults")
                self.recorder.record("decode_fault", {
                    "tick": self._tick_no, "consecutive": self._decode_faults,
                    "error": repr(exc)})
                if self._decode_faults > self.decode_fault_cap:
                    # persistently broken decode: fail the active batch
                    # with structured errors instead of spinning forever
                    detail = {"reason": "decode_failed",
                              "consecutive_faults": self._decode_faults,
                              "error": repr(exc)}
                    self.recorder.trip("decode_error", detail)
                    for st in list(self.scheduler.active()):
                        self._finish(st, "error",
                                     error={**detail,
                                            "rid": str(st.request.rid)})
                    self._decode_faults = 0
                return True
        self._decode_faults = 0
        with tracing.span("lm.decode_step", active=len(active)):
            with tracing.span("lm.decode.dispatch"):
                toks = np.zeros((self.max_batch, 1), np.int32)
                idxs = np.zeros((self.max_batch,), np.int32)
                for st in active:
                    toks[st.slot, 0] = st.tok
                    idxs[st.slot] = st.pos
                logits, self.pool.cache = self._dstep(
                    self.params, self.pool.cache, jnp.asarray(toks),
                    jnp.asarray(idxs))
            with tracing.span("lm.decode.fetch"):
                logits = np.asarray(logits)
            with tracing.span("lm.decode.sample"):
                for st in active:
                    st.tok = int(np.argmax(
                        logits[st.slot, 0, :self.cfg.vocab]))
                    st.pos += 1
                    st.out.append(st.tok)
                    if st.done:
                        self._finish(st, "ok")
                    elif not self.pool.extend(st.request.rid, st.pos + 1):
                        self._finish(st, "truncated")   # page budget gone
        return True

    # -- event loop ----------------------------------------------------------
    async def _submit_all(self, pending) -> None:
        for r in pending:
            delay = r.arrival - self._now()
            if delay > 0:
                await asyncio.sleep(delay)
            self.submit(r)

    def _fail_outstanding(self, requests, timeout_s: float) -> None:
        """Loop timeout: resolve every request that has no result yet with
        a structured timeout error and return its resources — active slots
        leave (KV pages freed through the normal _finish path), queued
        entries drop, never-submitted ones resolve too.  One serve_timeout
        trip captures the stuck state."""
        self.recorder.trip("serve_timeout", {
            "timeout_s": timeout_s, "done": len(self._results),
            "expected": len(requests),
            "queue_depth": self.scheduler.queue_depth(),
            "active": self.scheduler.n_active(),
            "telemetry": self.telemetry_snapshot()})
        for st in list(self.scheduler.active()):
            self._finish(st, "timeout",
                         error={"reason": "timeout", "phase": "decode",
                                "rid": str(st.request.rid),
                                "timeout_s": timeout_s,
                                "tokens_done": len(st.out)})
        for w in list(self.scheduler.waiting()):
            self.scheduler.remove(w)
        for r in requests:
            if r.rid not in self._results:
                self._results[r.rid] = ServeResult(
                    r.rid, [], "timeout", None,
                    error={"reason": "timeout", "phase": "queued",
                           "rid": str(r.rid), "timeout_s": timeout_s})

    async def run(self, requests: Sequence[ServeRequest],
                  timeout_s: float = 300.0) -> list:
        """Serve a trace of requests; returns ServeResults in input order.
        Every request terminates with a result or a structured error: a
        loop timeout resolves the outstanding requests (freeing their KV
        slots) instead of raising out of the loop."""
        self._t0 = time.perf_counter()
        pending = sorted(requests, key=lambda r: r.arrival)
        n_expected = len(pending)
        submitter = asyncio.ensure_future(self._submit_all(pending))
        try:
            while len(self._results) < n_expected:
                if self._now() > timeout_s:
                    self._fail_outstanding(requests, timeout_s)
                    break
                self._expire_deadlines()
                progressed = self._try_join()
                progressed = self._decode_tick() or progressed
                # yield so arrivals interleave with serving; back off when
                # idle (waiting on future arrivals)
                await asyncio.sleep(0 if progressed else 0.0005)
        finally:
            submitter.cancel()
        if self.plan_cache_dir:
            save_plan_cache(self.pc, self.plan_cache_dir)
        return [self._results[r.rid] for r in requests]

    def serve(self, requests: Sequence[ServeRequest],
              timeout_s: float = 300.0) -> list:
        """Synchronous wrapper around :meth:`run`.  Refuses to nest inside
        a running event loop (asyncio.run would raise a cryptic
        RuntimeError after partial work)."""
        try:
            asyncio.get_running_loop()
        except RuntimeError:
            return asyncio.run(self.run(requests, timeout_s=timeout_s))
        raise RuntimeError(
            "serve() was called from a running event loop; call "
            "`await runtime.run(requests, timeout_s=...)` instead")

    # -- analytical requests --------------------------------------------------
    def _trip_context(self) -> dict:
        """Incident context for executor_error trips: memory + occupancy
        state at failure time, not just the exception repr."""
        return {"ledger": self.ledger.snapshot(),
                "metrics": self.registry.report()}

    # -- multi-query analytics ------------------------------------------------
    def _analysis_exec(self, req: AnalysisRequest, keys=None):
        """One analytical query through the cross-query CSE path (subplan
        cache attached) or plain execution; returns (value, frontier
        info)."""
        with tracing.span("analysis.exec", rid=req.rid) as sp:
            if self.subplans is not None:
                out, info = mqo_run(req.planned, req.params, req.inputs,
                                    cache=self.subplans,
                                    versions=req.store_versions,
                                    aux=req.aux, keys=keys,
                                    tied_to=req.tied_to)
            else:
                out = req.planned(req.params, req.inputs, aux=req.aux)
                info = {"shared_hits": 0,
                        "executed": len(req.planned.concrete.nodes)}
            sp.set_metadata(executed=info["executed"],
                            shared_hits=info["shared_hits"])
            with tracing.span("analysis.device_wait"):
                jax.block_until_ready(out)
        return out, info

    @staticmethod
    def _leaf_sig(value) -> tuple:
        """Shape/dtype signature of a pytree — batchable queries must agree
        on it so stacking is well-formed."""
        return tuple((str(getattr(x, "dtype", type(x).__name__)),
                      tuple(getattr(x, "shape", ())))
                     for x in jax.tree.leaves(value))

    def _batch_group_key(self, req: AnalysisRequest, keys: dict) -> tuple:
        """Queries coalesce into one vmapped forward iff they share a plan,
        the same declared ``batch_param`` slot, the same *objects* for
        every other input, and the same batch-leaf shape/dtype.  Object
        identity is conservative (equal-but-distinct arrays miss the
        batch) but sound, and it is how multi-query workloads actually
        share bound payloads; the ids stay valid because the requests hold
        their inputs alive through the tick."""
        bp = req.batch_param
        fixed = tuple(sorted(
            (n, id(v)) for n, v in req.inputs.items() if n != bp))
        return (getattr(req.planned, "plan_id", id(req.planned)), bp,
                fixed, self._leaf_sig(req.inputs[bp]),
                "noparams" if not req.params else id(req.params))

    def _run_batched_group(self, leaders: list):
        """Execute same-shape queries as ONE vmapped planned forward over
        their stacked ``batch_param`` leaves; returns per-query values.
        vmap without jit: every primitive executes batched but *eagerly*,
        the same dispatch path the unbatched queries take."""
        bp = leaders[0].batch_param
        planned = leaders[0].planned
        fixed = {n: v for n, v in leaders[0].inputs.items() if n != bp}
        stacked = jax.tree.map(
            lambda *xs: jnp.stack([jnp.asarray(x) for x in xs], axis=0),
            *[r.inputs[bp] for r in leaders])
        params = leaders[0].params
        aux = leaders[0].aux

        def one(pv):
            return planned(params, {**fixed, bp: pv}, aux=aux)

        with tracing.span("analysis.batch", n=len(leaders),
                          rids=" ".join(str(r.rid) for r in leaders)):
            outs = jax.vmap(one)(stacked)
            jax.block_until_ready(outs)
        vals = [jax.tree.map(lambda x, _i=i: x[_i], outs)
                for i in range(len(leaders))]
        self.registry.count("analytics.batched", len(leaders))
        return vals

    def _root_key(self, req: AnalysisRequest, keys: dict) -> tuple:
        """The whole-query identity: plan id + the runtime keys of its
        outputs — two queries with equal root keys compute the same
        values, whatever their programs looked like."""
        return (getattr(req.planned, "plan_id", id(req.planned)),
                tuple(keys.get(o, o) for o in req.planned.concrete.outputs))

    def _settle_analysis(self, req: AnalysisRequest, res: AnalysisResult,
                         results: dict, t0: float) -> None:
        # route to the owning run_analyses call's results dict (a tick may
        # have drained a concurrent call's request); fall back to the
        # draining call's dict for requests with no registered owner
        sink, st0 = self._analysis_sinks.get(id(req), (results, t0))
        res.enqueued_at, res.settled_at = st0, time.perf_counter()
        res.ttfr_ms = (res.settled_at - st0) * 1e3
        self.registry.summary("analytics.ttfr_ms").observe(res.ttfr_ms)
        self.registry.count("analytics.requests")
        sink[req.rid] = res

    async def _admit_analysis_tick(self, tick: list, results: dict,
                                   t0: float) -> None:
        """One admission tick: key every drained query, dedupe exact twins
        (intra-tick groups + cross-task in-flight futures), coalesce
        same-shape queries into vmapped batches, run the rest through the
        CSE path, and resolve every request with a result.  The
        ``analysis.tick`` span covers the admission decision (keying,
        single-flight lookup, grouping), which runs before the tick's
        first await; execution opens spans of its own."""
        loop = asyncio.get_running_loop()
        groups: dict = {}        # root key -> [(req, keys), ...]
        waiters: list = []       # (req, future of an in-flight twin)
        with tracing.span("analysis.tick", n=len(tick)) as sp:
            for req in tick:
                keys = subdag_keys(req.planned, req.inputs,
                                   versions=req.store_versions,
                                   params=req.params)
                root = self._root_key(req, keys)
                fut = self._analysis_inflight.get(root)
                if fut is not None and root not in groups:
                    waiters.append((req, fut))
                    continue
                groups.setdefault(root, []).append((req, keys))
            # same-shape batching among group leaders (>=2 make a batch)
            singles, shaped = [], {}
            for root, members in groups.items():
                leader = members[0][0]
                if leader.batch_param is not None \
                        and leader.batch_param in leader.inputs:
                    gk = self._batch_group_key(leader, members[0][1])
                    shaped.setdefault(gk, []).append((root, members))
                else:
                    singles.append((root, members))
            vbatches = []
            for g in shaped.values():
                if len(g) >= 2:
                    vbatches.append(g)
                else:
                    singles.extend(g)
            sp.set_metadata(groups=len(groups),
                            twins=len(tick) - len(groups),
                            batches=len(vbatches))
        futs = {}
        for root, _ in singles:
            futs[root] = self._analysis_inflight[root] = loop.create_future()
        for g in vbatches:
            for root, _ in g:
                futs[root] = self._analysis_inflight[root] = \
                    loop.create_future()

        def resolve(root, members, payload, *, batched=False):
            # payload: (status, value, info, error, (started, finished))
            status, val, info, err, (started, finished) = payload
            fut = futs[root]
            if not fut.done():
                fut.set_result(payload)
            self._analysis_inflight.pop(root, None)
            for j, (req, _) in enumerate(members):
                if j > 0:
                    self.registry.count("analytics.deduped")
                res = AnalysisResult(
                    req.rid, val, status, err,
                    shared_hits=info.get("shared_hits", 0),
                    executed=info.get("executed", 0),
                    deduped=j > 0, batched=batched,
                    started_at=started, finished_at=finished)
                self._settle_analysis(req, res, results, t0)

        for g in vbatches:
            leaders = [members[0][0] for _, members in g]
            try:
                started = time.perf_counter()
                vals = self._run_batched_group(leaders)
                ran = (started, time.perf_counter())
                for (root, members), val in zip(g, vals):
                    resolve(root, members,
                            ("ok", val, {"executed": 1}, None, ran),
                            batched=True)
            except Exception as exc:
                # vmap refused the plan (data-dependent shapes, host
                # callbacks): run each leader through the CSE path instead
                self.recorder.record("batch_fallback", {
                    "n": len(g), "error": repr(exc)})
                singles.extend(g)
        for root, members in singles:
            leader, lkeys = members[0]
            started = time.perf_counter()
            try:
                val, info = self._analysis_exec(leader, keys=lkeys)
                resolve(root, members, ("ok", val, info, None,
                                        (started, time.perf_counter())))
            except Exception as exc:
                err = {"reason": "analysis_failed",
                       "plan_id": getattr(leader.planned, "plan_id", ""),
                       "error": repr(exc)}
                ran = (started, time.perf_counter())
                self.recorder.trip("executor_error",
                                   {**err, **self._trip_context()})
                resolve(root, members, ("error", None, {}, err, ran))
            await asyncio.sleep(0)   # let twins land on the future map
        for req, fut in waiters:
            status, val, info, err, (started, finished) = await fut
            self.registry.count("analytics.deduped")
            res = AnalysisResult(req.rid, val, status, err,
                                 shared_hits=info.get("shared_hits", 0),
                                 deduped=True, started_at=started,
                                 finished_at=finished)
            self._settle_analysis(req, res, results, t0)

    async def run_analyses(self, requests: Sequence[AnalysisRequest],
                           timeout_s: float = 300.0) -> list:
        """Serve a set of analytical queries through the multi-query
        admission path: per-tenant weighted round-robin drains up to
        ``analysis_tick`` queries per tick; each tick dedupes exact twins
        (single-flight — the first computes, the rest await its future),
        coalesces same-shape queries into one vmapped forward, and runs
        the remainder through the subplan-cache CSE pass.  Returns
        AnalysisResults in input order; every query resolves (errors are
        structured, a loop timeout resolves stragglers)."""
        t0 = time.perf_counter()
        results: dict = {}
        mine = {id(r) for r in requests}
        for r in requests:
            self._analysis_sinks[id(r)] = (results, t0)
            self.analysis_sched.enqueue(r, r.tenant)
        try:
            # completion is scoped to THIS call's requests: a tick may
            # settle a concurrent call's drained query into that call's
            # sink (or pick up extras), so len(results) alone can't gate
            while any(r.rid not in results for r in requests):
                if time.perf_counter() - t0 > timeout_s:
                    # pull this call's undrained stragglers out of the
                    # shared tenant queues so a later call can't adopt
                    # them, then resolve them with structured timeouts
                    self.analysis_sched.purge(lambda item: id(item) in mine)
                    for r in requests:
                        if r.rid not in results:
                            self._settle_analysis(r, AnalysisResult(
                                r.rid, None, "error",
                                {"reason": "timeout",
                                 "timeout_s": timeout_s}),
                                results, t0)
                    break
                tick = self.analysis_sched.drain(self.analysis_tick)
                if not tick:
                    await asyncio.sleep(0.0005)
                    continue
                await self._admit_analysis_tick(tick, results, t0)
                await asyncio.sleep(0)
        finally:
            for r in requests:
                self._analysis_sinks.pop(id(r), None)
        self._maybe_snapshot(force=True)
        return [results[r.rid] for r in requests]

    def serve_analyses(self, requests: Sequence[AnalysisRequest],
                       timeout_s: float = 300.0) -> list:
        """Synchronous wrapper around :meth:`run_analyses` (same nesting
        rule as :meth:`serve`)."""
        try:
            asyncio.get_running_loop()
        except RuntimeError:
            return asyncio.run(self.run_analyses(requests,
                                                 timeout_s=timeout_s))
        raise RuntimeError(
            "serve_analyses() was called from a running event loop; call "
            "`await runtime.run_analyses(...)` instead")

    def run_analysis(self, planned, params, inputs: dict, *,
                     analyze: bool = False, aux: Optional[dict] = None,
                     deadline_s: Optional[float] = None,
                     degrade=None, store_versions: tuple = (),
                     tied_to=None):
        """Execute an analytical (tri-store) :class:`PlannedFunction`
        through the runtime's shared metrics registry, so LM and
        analytical traffic report into one place: wall time lands in the
        ``analytics.run_ms`` summary, request/trace counts in
        ``analytics.*`` counters.  With ``analyze=True`` the run goes
        through ``PlannedFunction.analyze`` (EXPLAIN ANALYZE tracing) and
        the trace's wall/sync split is recorded too.  Either path feeds the
        flight recorder: traced runs land their RunTrace summary in the
        ring (and trip a dump on BoundedRel overflow, inside ``analyze``);
        an executor exception trips an ``executor_error`` dump carrying the
        current ledger snapshot + metrics report.

        ``degrade``: with a :class:`~repro.serving.degrade.DegradePolicy`
        attached to the runtime, a standing query is transparently switched
        to its cheaper variant under overload — pass an int to force a
        ladder level, ``False`` to opt this call out.  ``deadline_s``
        bounds the run's wall time *post hoc*: a miss lands an
        ``analytics.deadline_miss`` count and a recorder event (analytical
        plans execute as one JAX computation — there is no token boundary
        to cancel at, so the deadline informs shedding, not abortion)."""
        if degrade is not False and self.degrade is not None:
            lvl = degrade if isinstance(degrade, int) \
                and not isinstance(degrade, bool) else \
                self.degrade.level(
                    queue_depth=self.scheduler.queue_depth(),
                    max_batch=self.max_batch,
                    kv_fill=self.pool.occupancy()["fill"])
            if lvl > 0:
                planned = self.degrade.replan(planned, lvl, cache=self.pc)
        if self.faults is not None and planned.faults is None:
            planned.faults = self.faults
        t0 = time.perf_counter()
        try:
            if analyze:
                outs = planned.analyze(params, inputs, aux=aux,
                                       recorder=self.recorder,
                                       trip_context=self._trip_context)
                tr = planned.last_run_trace
                self.registry.summary("analytics.trace_wall_ms").observe(
                    tr.wall_ms)
                self.registry.count("analytics.traced")
            elif self.subplans is not None and planned.faults is None:
                # cross-query CSE: reuse cached sub-DAG intermediates and
                # execute only the residual suffix (bitwise-identical — the
                # reused values are an identical computation's arrays)
                outs, _info = mqo_run(planned, params, inputs,
                                      cache=self.subplans,
                                      versions=store_versions, aux=aux,
                                      tied_to=tied_to)
                jax.block_until_ready(outs)
            else:
                outs = planned(params, inputs, aux=aux)
                jax.block_until_ready(outs)
        except Exception as exc:
            # analyze() already tripped for its own failures (with the same
            # trip context); only the untraced path needs capture here
            if not analyze:
                self.recorder.trip("executor_error", {
                    "plan_id": getattr(planned, "plan_id", ""),
                    "error": repr(exc), **self._trip_context()})
            raise
        elapsed_s = time.perf_counter() - t0
        if deadline_s is not None and elapsed_s > deadline_s:
            self.registry.count("analytics.deadline_miss")
            self.recorder.record("deadline_miss", {
                "plan_id": planned.plan_id, "kind": "analysis",
                "deadline_s": deadline_s, "elapsed_s": elapsed_s})
        self.registry.summary("analytics.run_ms").observe(elapsed_s * 1e3)
        self.registry.count("analytics.requests")
        self._maybe_snapshot(force=True)
        return outs


def serve_sequential(model: LM, params, requests: Sequence[ServeRequest], *,
                     max_seq: int = 128, bucket_lo: int = 8,
                     engines=("xla",), syscat=None, plan_cache=None,
                     jit_memo: Optional[dict] = None) -> list:
    """The sequential seed path, as a baseline: one request at a time —
    planned (bucketed, cached) prefill for the prompt logits, prompt replay
    through the cached decode path to build the KV cache, then
    token-by-token decode at batch 1.  What ``launch/serve.py`` did before
    the async runtime; kept for the throughput benchmark's comparison."""
    syscat = syscat or SystemCatalog()
    pc = plan_cache if plan_cache is not None else default_plan_cache()
    cfg = model.cfg
    # ``jit_memo`` (caller-held) keeps the jitted step/prefills warm across
    # invocations — the benchmark warms the baseline with it so the
    # comparison against the runtime excludes compile time on both sides
    jitted = jit_memo if jit_memo is not None else {}
    if "__dstep__" not in jitted:
        jitted["__dstep__"] = jax.jit(
            lambda p, c, t, i: decode_step(model, p, c, t, i))
    dstep = jitted["__dstep__"]
    results = []
    for req in requests:
        bucket = bucket_len(req.prompt_len, lo=bucket_lo, hi=max_seq)
        plan = model.build_plan(1, bucket, mode="prefill")
        fwd = plan_and_compile(plan, CATALOG, syscat, engines=engines,
                               cache=pc)
        jf = jitted.get(fwd.plan_id)
        if jf is None:
            def jf(p, toks, n, _f=fwd):
                logits = _f(p, {"tokens": toks})
                row = jax.lax.dynamic_index_in_dim(logits, n - 1, axis=1,
                                                   keepdims=False)
                return jnp.argmax(row[0, :cfg.vocab]).astype(jnp.int32)
            jf = jitted[fwd.plan_id] = jax.jit(jf)
        padded_np = np.zeros((1, bucket), np.int32)
        padded_np[0, :req.prompt_len] = req.prompt
        tok = int(jf(params, jnp.asarray(padded_np),
                     jnp.int32(req.prompt_len)))
        cache = init_cache(model, 1, max_seq)
        for t in range(req.prompt_len):
            _, cache = dstep(params, cache,
                             jnp.asarray(padded_np[:, t:t + 1]),
                             jnp.int32(t))
        out = [tok]
        for t in range(req.prompt_len, req.prompt_len + req.gen - 1):
            lg, cache = dstep(params, cache,
                              jnp.asarray([[tok]], jnp.int32), jnp.int32(t))
            tok = int(jnp.argmax(lg[0, 0, :cfg.vocab]))
            out.append(tok)
        results.append(ServeResult(req.rid, out, "ok", None))
    return results
