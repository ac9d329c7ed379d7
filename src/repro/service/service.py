"""The :class:`RLCService` facade: build -> freeze -> device -> serve.

Wires the whole serving path together::

    g = erdos_renyi(500, 4.0, 4)
    svc = RLCService.build(g, ServiceConfig(k=2, batch_size=16))
    svc.query(3, 17, "(0 1)+")                  # single, through the cache
    svc.query_batch([(s, t, "(a b)+"), ...])    # micro-batched

Admission: a call is checked whole before any of it is queued, each
distinct constraint parsed/validated/canonicalized to a minimum repeat
once (:mod:`repro.service.expr`); each query is then checked against
the result cache and — on miss — handed to the micro-batcher. Flushed
batches run on the executor (device backend with python fallback);
answers backfill the cache. ``query_batch`` is synchronous: it drains
the scheduler before returning, so every admitted query is answered in
admission order.

Within a call, a flushed batch is dispatched to the executor and the
loop goes back to admitting while the device answers it. At each flush
the oldest batches whose answers are back are answered; after the drain
the call waits for the rest. A facade whose ``_run_batch`` is overridden
(the sharded fan-out) runs each batch to the end when it flushes.

Phase spans (:meth:`repro.obs.Observability.phase`, always on): the
whole call (``query_batch``), each run of admissions between flushes
(``admit``), each batch's dispatch (``execute``) and, later, its
readback with the controller update and answer fan-out (``resolve``,
around ``answer``). Where each batch runs to the end at once,
``execute`` holds all of it, ``answer`` included.
"""
from __future__ import annotations

import time
from collections import Counter, deque
from dataclasses import dataclass
from typing import (Callable, Deque, Dict, List, Optional, Sequence, Tuple,
                    Union)

import numpy as np

from repro.build import BuildStats, build_rlc_index_with_stats
from repro.core.graph import LabeledGraph
from repro.core.minimum_repeat import LabelSeq, mr_id_space
from repro.core.rlc_index import RLCIndex
from repro.obs import Observability

from .answer import SHED, Answer
from .cache import ResultCache
from .control import ControlPlane
from .executor import BatchExecutor, PendingBatch
from .expr import PathExpression, canonicalize, parse_expression
from .scheduler import Batch, MicroBatcher, Request

Constraint = Union[str, Sequence[int], PathExpression]
Query = Tuple[int, int, Constraint]


@dataclass
class ServiceConfig:
    k: int = 2
    batch_size: int = 32
    max_wait_ms: float = 2.0
    cache_capacity: int = 4096
    cache_ttl_s: Optional[float] = None   # optional TTL on cached answers
    backend: str = "auto"           # "auto" | "pallas" | "sorted" | "numpy" | "python"
    build_backend: str = "auto"     # repro.build backend for (re)builds
    use_device: bool = True         # build the padded DeviceIndex layout
    label_names: Optional[Dict[str, int]] = None  # e.g. {"knows": 0, ...}
    #: incremental-build budget for apply_delta (see DeltaBuilder);
    #: 1.0 disables the full-rebuild fallback
    delta_fallback_frac: float = 0.25
    #: metrics registry on/off (counters and histograms, default-on —
    #: cheap). Off replaces every cell with the null registry.
    telemetry: bool = True
    #: fraction of query_batch calls that record spans (0 = tracing off)
    trace_sample_rate: float = 0.0
    #: span buffer bound; past it spans are dropped and counted
    trace_max_events: int = 50_000
    #: fraction of answered queries re-executed against the BiBFS oracle
    #: by the shadow verifier (0 = shadow verification off)
    shadow_sample_rate: float = 0.0
    #: shadow queue bound; past it the oldest pending check is dropped
    shadow_max_pending: int = 1024
    #: run shadow checks on a background thread (else they run when
    #: drained explicitly or at snapshot time)
    shadow_background: bool = False
    # -- control plane (repro.service.control) --------------------------- #
    #: per-query p99 latency SLO; setting it turns on the SLO batch
    #: controller (per-MR-length batch sizes + deadlines replace the
    #: fixed batch_size/max_wait_ms above)
    target_p99_ms: Optional[float] = None
    #: minimum time between controller parameter recomputations
    control_interval_s: float = 0.05
    #: ceiling for controller-grown batch sizes (None -> 4 * batch_size)
    max_batch_size: Optional[int] = None
    #: hard admission bound: scheduler pending depth past which arrivals
    #: are shed (or evict a lower-priority queued request); None = off
    admission_max_pending: Optional[int] = None
    #: soft back-pressure: shed low-priority arrivals while the EWMA
    #: queue wait exceeds this (None -> 2 * target_p99_ms when the SLO
    #: controller is on, else off)
    admission_backpressure_ms: Optional[float] = None
    #: hot-key candidates tracked for warming; > 0 turns the prioritized
    #: cache warmer on (it runs after apply_delta / hot_swap)
    warm_capacity: int = 0
    #: warming budgets: estimated cache bytes written / wall seconds
    warm_budget_bytes: int = 1 << 20
    warm_budget_s: float = 0.25
    #: injectable scheduler clock (e.g. control.VirtualClock for open-loop
    #: overload replay); None = time.monotonic
    clock: Optional[Callable[[], float]] = None


def bind_phases(svc) -> None:
    """Bind the facade's phase spans and admission counter on ``svc``
    (both facades share the admission loop that enters them)."""
    svc._m_parsed = svc.obs.registry.counter(
        "rlc_admit_parsed",
        desc="constraints parsed and canonicalized at admission").labels()
    ph = svc.obs.phase
    svc._ph_query_batch = ph("query_batch", cat="service")
    svc._ph_admit = ph("admit", cat="admission")
    svc._ph_execute = ph("execute", cat="service")
    svc._ph_resolve = ph("resolve", cat="service")
    svc._ph_answer = ph("answer", cat="service")


class RLCService:
    def __init__(self, graph: LabeledGraph, index: RLCIndex,
                 config: ServiceConfig,
                 build_stats: Optional[BuildStats] = None,
                 obs: Optional[Observability] = None):
        self.graph = graph
        self.index = index
        self.config = config
        self.build_stats = build_stats   # None when the index was adopted
        # one telemetry context for the whole stack (passed in by build()
        # so offline build phases land in the same registry)
        self.obs = obs or Observability(
            enabled=config.telemetry,
            trace_sample_rate=config.trace_sample_rate,
            max_trace_events=config.trace_max_events)
        self.mr_ids = mr_id_space(graph.num_labels, config.k)
        self._id_to_mr: List[LabelSeq] = [
            mr for mr, _ in sorted(self.mr_ids.items(), key=lambda kv: kv[1])]
        t0 = time.perf_counter()
        self.frozen = index.freeze(self.mr_ids)
        t1 = time.perf_counter()
        self.device_index = self._make_device_index()
        #: host seconds of the serving setup: the CSR freeze, and the
        #: padded device layout (row packing, key sort, transfer enqueue)
        self.setup_seconds = dict(freeze=t1 - t0,
                                  device=time.perf_counter() - t1)
        self.executor = BatchExecutor(
            index, self.frozen, self.device_index, self._id_to_mr,
            backend=config.backend, obs=self.obs)
        self.cache = ResultCache(config.cache_capacity,
                                 ttl_s=config.cache_ttl_s, obs=self.obs)
        clock = config.clock if config.clock is not None else time.monotonic
        self.ctl = ControlPlane.from_config(
            config, self.obs, self.cache, self._warm_execute, clock)
        self.batcher = MicroBatcher(
            config.batch_size, config.max_wait_ms * 1e-3,
            clock=clock, obs=self.obs,
            params_fn=(self.ctl.slo.params
                       if self.ctl.slo is not None else None))
        self.queries_served = 0
        self.queries_shed = 0
        self.deltas_applied = 0
        self._delta = None          # lazy DeltaBuilder (apply_delta)
        self._engine = None         # lazy AsyncEngine (start()/submit())
        self._closed = False
        self._last_audit = None     # most recent audit_report() document
        self._m_explain = self.obs.registry.counter(
            "rlc_explain_requests",
            desc="EXPLAIN bundles produced, by witness kind",
            labelnames=("kind",))
        bind_phases(self)
        from repro.obs.shadow import attach_shadow
        self._shadow = attach_shadow(self)

    # ------------------------------------------------------------------ #
    @classmethod
    def build(cls, graph: LabeledGraph,
              config: Optional[ServiceConfig] = None,
              index: Optional[RLCIndex] = None) -> "RLCService":
        """Build (or adopt) the RLC index for ``graph`` and start serving.
        Builds go through the configured :mod:`repro.build` backend."""
        config = config or ServiceConfig()
        obs = Observability(enabled=config.telemetry,
                            trace_sample_rate=config.trace_sample_rate,
                            max_trace_events=config.trace_max_events)
        build_stats = None
        if index is None:
            index, build_stats = build_rlc_index_with_stats(
                graph, config.k, backend=config.build_backend,
                observer=obs.build_observer())
        elif index.k != config.k:
            raise ValueError(
                f"index built with k={index.k} but config.k={config.k}")
        return cls(graph, index, config, build_stats=build_stats, obs=obs)

    # -- admission ------------------------------------------------------ #
    def parse(self, constraint: Constraint) -> PathExpression:
        if isinstance(constraint, PathExpression):
            return constraint
        if isinstance(constraint, str):
            return parse_expression(
                constraint, num_labels=self.graph.num_labels,
                k=self.config.k, label_names=self.config.label_names)
        return canonicalize(constraint, num_labels=self.graph.num_labels,
                            k=self.config.k)

    def _resolve(self, constraint: Constraint) -> Tuple[int, int]:
        """``(mr_id, mr_len)`` of one constraint: parsed, checked and
        canonicalized (counted in ``rlc_admit_parsed``)."""
        self._m_parsed.inc()
        expr = self.parse(constraint)
        return self.mr_ids[expr.mr], len(expr.mr)

    def _admit(self, s: int, t: int, constraint: Constraint
               ) -> Tuple[int, int, int, int]:
        (key,), (mr_len,) = self._admit_call(((s, t, constraint),))
        return (*key, mr_len)

    def _admit_call(self, queries: Sequence[Query]
                    ) -> Tuple[List[Tuple[int, int, int]], List[int]]:
        """Check and resolve a whole call before any of it is admitted:
        ``(keys, mr_lens)``, the canonical ``(s, t, mr_id)`` and MR length
        of each query. A hashable constraint is resolved on its first
        appearance in the call only, an unhashable one (a list) each
        time. The first bad query raises, and nothing is queued."""
        n = self.graph.num_vertices
        resolved: Dict[Constraint, Tuple[int, int]] = {}
        keys: List[Tuple[int, int, int]] = []
        lens: List[int] = []
        for s, t, constraint in queries:
            s, t = int(s), int(t)
            if not (0 <= s < n and 0 <= t < n):
                raise ValueError(
                    f"vertex ids ({s}, {t}) out of range [0, {n})")
            try:
                mr = resolved.get(constraint)
            except TypeError:       # unhashable
                mr = self._resolve(constraint)
            else:
                if mr is None:
                    mr = resolved[constraint] = self._resolve(constraint)
            keys.append((s, t, mr[0]))
            lens.append(mr[1])
        return keys, lens

    # -- serving -------------------------------------------------------- #
    def query(self, s: int, t: int, constraint: Constraint) -> Answer:
        """Synchronous single query (cache -> batch-of-one on miss).
        Returns a typed :class:`Answer` — truthy/comparable exactly like
        the bool it wraps, plus disposition + backend attribution."""
        return self.query_batch([(s, t, constraint)])[0]

    def query_batch(self, queries: Sequence[Query],
                    now: Optional[float] = None) -> List[Answer]:
        """Answer ``queries`` in order through cache + scheduler + executor.

        Each answer is a typed :class:`Answer` (value + disposition +
        backend attribution); ``bool(ans)`` / ``ans == True`` behave
        like the bare boolean this method used to return.

        ``now``: optional admission timestamp (for replaying a timed
        arrival trace); defaults to the scheduler's clock per admission.

        With admission control on (``admission_max_pending`` /
        ``admission_backpressure_ms``), a dropped query's answer is the
        :data:`SHED` sentinel — never a fabricated boolean; check
        ``ans is SHED`` or ``ans.shed`` (SHED raises on ``bool()``).
        Eviction of queued victims assumes the synchronous single-caller
        contract this method already requires (see the lost-answer guard
        below): a victim admitted by a concurrent call would trip that
        guard there.

        When the async engine is running (:meth:`start`), the scheduler
        is ticker-driven and shared with :meth:`submit` callers, so this
        method bridges through the engine instead of draining the
        batcher itself — same answers, no lost-flush race.
        """
        with self._ph_query_batch():
            if self._engine is not None and self._engine.active:
                futures = [self.submit(s, t, c) for (s, t, c) in queries]
                self._engine.flush()
                return [f.result(timeout=60.0) for f in futures]
            # one sampled trace per query_batch call; None on the unsampled
            # hot path, so every span below is a single comparison away
            tr = self.obs.tracer.maybe_trace()
            admission = self.ctl.admission
            observe, probe = self.ctl.observe_admit, self.cache.probe
            submit = self.batcher.submit
            # the call's cache hits and expiries by (outcome, mr_len), and
            # how many queries were probed: counted once, in `finally`
            found: Dict[Tuple[str, Optional[int]], int] = {}
            lens: List[int] = []
            probed = 0
            # batches dispatched and not yet answered, oldest first; None
            # where _run_batch is overridden (the sharded fan-out), which
            # runs each batch to the end when it flushes
            inflight: Optional[Deque[Tuple[Batch, PendingBatch, float]]] = (
                deque() if getattr(self._run_batch, "__func__", None)
                is RLCService._run_batch else None)
            # one admit span per run of admissions: it closes while
            # batches are dispatched and answered, and reopens after
            admit = self._ph_admit().open()
            try:
                # the whole call is checked before any of it is queued
                keys, lens = self._admit_call(queries)
                answers: List[Optional[Answer]] = [None] * len(keys)
                # scheduler req_id -> output positions (> 1 when duplicate
                # in-flight queries were coalesced onto one request)
                slot: Dict[int, List[int]] = {}
                for i, key in enumerate(keys):
                    t0 = tr.tracer._now() if tr is not None else 0.0
                    mr_len = lens[i]
                    # the frequency sketch counts every arrival (hits
                    # included): key popularity is a property of the
                    # workload, not of the cache's current contents
                    observe(key, mr_len)
                    hit = probe(key, found, mr_len)
                    probed = i + 1
                    if tr is not None:
                        tr.add(f"admit[{i}]", t0, tr.tracer._now() - t0,
                               cat="admission", mr_len=mr_len,
                               cache="hit" if hit is not None else "miss")
                    if hit is not None:
                        answers[i] = Answer(hit, "cache_hit")
                        continue
                    if admission is not None:
                        decision, victim = admission.decide(
                            key, mr_len, self.batcher)
                        if decision == "shed":
                            answers[i] = SHED
                            continue
                        if (decision == "evict"
                                and self.batcher.evict(victim)):
                            # the victim's submitters get the explicit SHED
                            for pos in slot.pop(victim.req_id, ()):
                                answers[pos] = SHED
                    req, ready = submit(*key, mr_len, now)
                    pos = slot.get(req.req_id)
                    if pos is None:
                        slot[req.req_id] = [i]
                    else:
                        pos.append(i)
                    if ready:
                        admit.close()
                        self._serve(ready, inflight, answers, slot, tr)
                        admit.open()
            finally:
                self.cache.count(Counter(lens[:probed]), found)
                admit.close()
            self._serve(self.batcher.drain(), inflight, answers, slot, tr,
                        wait=True)
            if any(a is None for a in answers):
                # a batch was flushed outside this call (ticker thread or
                # a concurrent query_batch stealing a coalesced key) —
                # fail loud rather than coerce the hole to False
                raise RuntimeError(
                    "query_batch lost answers to an external flush; do not "
                    "share a ticker-driven or concurrent MicroBatcher with "
                    "synchronous query_batch")
            self.queries_served += len(queries)
            out: List[Answer] = answers
            self.queries_shed += sum(1 for a in out if a.shed)
            if self._shadow is not None:
                for (s, t, mr_id), ans in zip(keys, out):
                    if not ans.shed:        # no answer to verify
                        self._shadow.offer(s, t, mr_id, ans.value)
            return out

    def _run_batch(self, batch: Batch, tr=None):
        """Produce one answer per real request, plus per-request backend
        attribution: ``(values, backends)`` where ``backends`` is one
        label per request. Overridden by the sharded service, which fans
        the batch out across shards instead."""
        ans, backend = self.executor.execute(
            batch.s, batch.t, batch.mr_id, batch.n_real, trace=tr)
        return ans, [backend] * len(batch.requests)

    def _warm_execute(self, s: np.ndarray, t: np.ndarray,
                      mr_id: np.ndarray, mr_len: int) -> np.ndarray:
        """Cache-warmer execution hook: answer hot keys through the same
        batch path queries take (the sharded override of ``_run_batch``
        fans warm batches across shards too). Bypasses the scheduler —
        warming is off the serving critical path by construction."""
        reqs = [Request(-1 - i, int(s[i]), int(t[i]), int(mr_id[i]),
                        int(mr_len)) for i in range(len(s))]
        batch = Batch(reqs, np.asarray(s, np.int32),
                      np.asarray(t, np.int32),
                      np.asarray(mr_id, np.int32), int(mr_len), "warm")
        vals, _backends = self._run_batch(batch)
        return np.asarray(vals, dtype=bool)

    def _serve(self, batches: List[Batch],
               inflight: Optional[Deque[Tuple[Batch, PendingBatch, float]]],
               answers: List[Optional[Answer]], slot: Dict[int, List[int]],
               tr=None, wait: bool = False) -> None:
        """Run flushed ``batches`` for :meth:`query_batch`. Each is
        dispatched and queued in ``inflight``; then the batches at its
        head are answered while their answers are back, or all of them
        when ``wait``. With no queue (``inflight`` None) each batch runs
        to the end here."""
        if inflight is None:
            for batch in batches:
                self._execute(batch, answers, slot, tr)
            return
        for batch in batches:
            self._queue_wait(batch, tr)
            with self._ph_execute(tr, n=batch.n_real, mr_len=batch.mr_len):
                t0 = time.perf_counter()
                pending = self.executor.dispatch(
                    batch.s, batch.t, batch.mr_id, batch.n_real, trace=tr)
                inflight.append((batch, pending,
                                 time.perf_counter() - t0))
        while inflight and (wait or inflight[0][1].done()):
            batch, pending, dispatch_s = inflight.popleft()
            with self._ph_resolve(tr, n=batch.n_real, mr_len=batch.mr_len):
                t0 = time.perf_counter()
                vals, backend = self.executor.execute(
                    batch.s, batch.t, batch.mr_id, batch.n_real, trace=tr,
                    pending=pending)
                self._answer(batch, vals, [backend] * len(batch.requests),
                             dispatch_s + time.perf_counter() - t0,
                             answers, slot, tr)

    def _queue_wait(self, batch: Batch, tr) -> None:
        if tr is not None:
            # queue wait is measured on the scheduler's clock; only the
            # duration crosses into the tracer's timeline
            oldest = min(r.enqueued_at for r in batch.requests)
            tr.add_ending_now("queue_wait",
                              max(batch.flushed_at - oldest, 0.0),
                              cat="batcher", reason=batch.reason,
                              mr_len=batch.mr_len, n=batch.n_real)

    def _execute(self, batch: Batch, answers: List[Optional[Answer]],
                 slot: Dict[int, List[int]], tr=None) -> None:
        """Run one batch to the end: ``_run_batch``, then the answers."""
        self._queue_wait(batch, tr)
        with self._ph_execute(tr, n=batch.n_real, mr_len=batch.mr_len):
            t0 = time.perf_counter()
            vals, backends = self._run_batch(batch, tr)
            self._answer(batch, vals, backends, time.perf_counter() - t0,
                         answers, slot, tr)

    def _answer(self, batch: Batch, vals, backends, exec_s: float,
                answers: List[Optional[Answer]], slot: Dict[int, List[int]],
                tr=None) -> None:
        """A batch's answers into the control loops, the cache and each
        submitter's position; ``exec_s`` is the executor's time on it."""
        with self._ph_answer(tr):
            # feed the control loops (SLO EWMAs, back-pressure queue
            # waits); a VirtualClock scheduler clock also advances by
            # the measured execute time so open-loop replay
            # accumulates realistic waits
            self.ctl.on_batch_executed(batch, exec_s)
            advance = getattr(self.batcher.clock, "advance", None)
            if advance is not None:
                advance(exec_s)
            for req, val, backend in zip(batch.requests, vals, backends):
                val = bool(val)
                self.cache.put((req.s, req.t, req.mr_id), val,
                               mr_len=batch.mr_len)
                ans = Answer(val, "degraded" if backend == "bibfs"
                             else "computed", backend)
                for pos in slot.get(req.req_id, ()):
                    answers[pos] = ans

    # -- EXPLAIN / provenance -------------------------------------------- #
    def explain(self, s: int, t: int, constraint: Constraint,
                max_hubs: int = 8) -> dict:
        """Answer ``(s, t, constraint)`` with its full derivation.

        The bundle carries the witness the serving join path would
        produce (``repro.obs.witness/1``: Case-2 entries / Case-1 join
        hubs for positives, the ruling-out fact for negatives), which
        backend explained it, and the *disposition* the query would get
        right now — whether the answer is sitting in the result cache
        and whether an identical key is in-flight in the micro-batcher.
        Read-only: no cache mutation, no batch slot, no served-query
        accounting; when a trace is sampled it lands as one ``explain``
        span.
        """
        tr = self.obs.tracer.maybe_trace()
        t0 = tr.tracer._now() if tr is not None else 0.0
        s, t, mr_id, _mr_len = self._admit(s, t, constraint)
        key = (s, t, mr_id)
        bundle = self._explain_admitted(s, t, mr_id, max_hubs=max_hubs)
        cached = self.cache.peek(key)
        bundle.update(
            s=s, t=t, mr_id=mr_id, mr=list(self._id_to_mr[mr_id]),
            cache=dict(
                disposition="hit" if cached is not None else "miss",
                answer=cached),
            coalesced=self.batcher.is_inflight(key))
        kind = bundle["witness"].get("kind", "unknown")
        if tr is not None:
            tr.add("explain", t0, tr.tracer._now() - t0, cat="explain",
                   answer=bundle["answer"], backend=bundle["backend"],
                   kind=kind)
        self._m_explain.labels(kind=kind).inc()
        return bundle

    def _explain_admitted(self, s: int, t: int, mr_id: int,
                          max_hubs: int = 8) -> dict:
        """Backend dispatch for one admitted query (single-host: the
        executor's chain; overridden by the sharded service to add
        routing hops)."""
        import numpy as np
        ws, backend = self.executor.explain_batch(
            np.array([s]), np.array([t]), np.array([mr_id]),
            max_hubs=max_hubs)
        return dict(answer=ws[0]["answer"], backend=backend,
                    witness=ws[0])

    # -- incremental graph mutation -------------------------------------- #
    def _delta_backend_name(self) -> str:
        # "parallel" maps to its sequential batched equivalent: delta
        # rebuilds touch a dirty phase subset too small to amortize the
        # epoch/merge protocol
        b = self.config.build_backend
        return b if b not in ("auto", "python", "parallel") else "numpy"

    def _make_device_index(self):
        """The padded device layout of ``self.frozen``; None only when
        ``use_device`` is off. A layout that cannot be built raises."""
        if not self.config.use_device:
            return None
        from repro.core.device_index import DeviceIndex
        return DeviceIndex.from_frozen(self.frozen, self.mr_ids)

    def _ensure_delta_builder(self):
        """Bootstrap the incremental builder on first use: one traced
        full (re)build of the current graph. If the serving index was
        *adopted* pre-built (possibly with non-default pruning flags),
        the whole serving state is resynced to the rebuilt index — the
        later partial re-freezes patch rows against the builder's entry
        sets, so serving a different vintage would leave stale entries
        in rows the builder never marks dirty."""
        if self._delta is None:
            from repro.build.delta import DeltaBuilder
            adopted = self.build_stats is None
            db = DeltaBuilder(
                self.graph, self.config.k,
                backend=self._delta_backend_name(),
                fallback_frac=self.config.delta_fallback_frac,
                obs=self.obs)
            db.full()
            if adopted:
                # may itself clear self._delta (sharded hot_swap), so
                # assign the builder only afterwards
                self._adopt_rebuilt_index(db)
            self._delta = db
        return self._delta

    def _adopt_rebuilt_index(self, db) -> None:
        """Swap the full serving state onto the delta builder's index
        (bootstrap over an adopted index; see _ensure_delta_builder)."""
        self.index = db.index
        self.build_stats = db.stats
        self.frozen = self.index.freeze(self.mr_ids)
        self.device_index = self._make_device_index()
        self.executor.index = self.index
        self.executor.frozen = self.frozen
        self.executor.device_index = self.device_index
        self.cache.clear()

    def apply_delta(self, delta) -> dict:
        """Apply a :class:`repro.core.graph.GraphDelta` end-to-end.

        Incrementally re-derives the index (:mod:`repro.build.delta`),
        re-freezes only the dirty/re-sorted row ranges, refreshes the
        device layout, and evicts exactly the cached answers whose
        ``(s, t)`` rows went dirty — everything else keeps serving from
        cache. Returns a summary dict (delta accounting + evictions).
        """
        # fence in-flight warm work first: answers computed against the
        # pre-delta index must never land in the post-delta cache
        self.ctl.bump_epoch()
        db = self._ensure_delta_builder()
        res = db.apply(delta)
        self.graph = db.graph
        self.index = db.index
        self.build_stats = res.stats
        if res.fallback:
            self.frozen = self.index.freeze(self.mr_ids)
        else:
            self.frozen = self.frozen.patch_rows(
                self.index, self.mr_ids,
                set(res.dirty_out.tolist()) | set(res.resort_out.tolist()),
                set(res.dirty_in.tolist()) | set(res.resort_in.tolist()))
        self.device_index = self._make_device_index()
        # the executor keeps its latency recorders; only the index
        # references move. Repoint BEFORE invalidating the cache: a
        # concurrent ticker flush that executed on the old index must not
        # be able to re-cache a stale answer for a just-evicted key.
        self.executor.index = self.index
        self.executor.frozen = self.frozen
        self.executor.device_index = self.device_index
        if res.fallback:
            evicted = len(self.cache)
            self.cache.clear()
        else:
            evicted = self.cache.invalidate_rows(
                dirty_s=set(res.dirty_out.tolist()),
                dirty_t=set(res.dirty_in.tolist()))
        self.deltas_applied += 1
        if self._shadow is not None:
            # pending checks were served by the pre-delta index; the
            # oracle now walks the mutated graph, so they'd diverge
            # spuriously
            self._shadow.discard_pending()
        # re-materialize the hot Zipf head against the new index, under
        # the warmer's byte/time budget (no-op when warming is off)
        warm = self.ctl.warm("apply_delta")
        return dict(delta=res.as_dict(), cache_evicted=evicted,
                    dirty_out=res.dirty_out.tolist(),
                    dirty_in=res.dirty_in.tolist(),
                    deltas_applied=self.deltas_applied,
                    warm=warm)

    # -- lifecycle -------------------------------------------------------- #
    def start(self, tick_interval_s: float = 0.002) -> "RLCService":
        """Bring up async admission: after ``start()``, :meth:`submit`
        returns immediately with a future and batches execute on a
        background thread (deadline-ticker driven). Idempotent; returns
        ``self`` so ``with svc.start():`` reads naturally. Synchronous
        :meth:`query` / :meth:`query_batch` keep working (they bridge
        through the engine). The sharded service shares this exact
        protocol — one lifecycle across both facades."""
        if self._closed:
            raise RuntimeError("service is closed")
        if self._engine is None:
            from .lifecycle import AsyncEngine
            self._engine = AsyncEngine(self, tick_interval_s)
        self._engine.start()
        return self

    def submit(self, s: int, t: int, constraint: Constraint,
               now: Optional[float] = None):
        """Non-blocking query: admission happens now, execution happens
        on the engine thread; returns a
        :class:`concurrent.futures.Future` resolving to an
        :class:`Answer` (or :data:`SHED` under admission control).
        Starts the engine on first use."""
        if self._engine is None or not self._engine.active:
            self.start()
        return self._engine.submit(s, t, constraint, now)

    def close(self) -> None:
        """Idempotent shutdown: drain + stop the async engine (resolving
        every in-flight future), stop the background deadline ticker and
        the shadow verifier. Safe to call any number of times; the
        service can keep answering synchronous queries afterwards."""
        if self._closed:
            return
        self._closed = True
        if self._engine is not None:
            self._engine.close()
        self.batcher.stop_ticker()
        if self._shadow is not None:
            self._shadow.stop()

    def __enter__(self) -> "RLCService":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.close()
        return False

    # -- deprecated lifecycle entry points -------------------------------- #
    def start_ticker(self, on_batch=None,
                     interval_s: Optional[float] = None) -> None:
        """Deprecated: use :meth:`start`. Kept as a shim for callers
        that drove the scheduler ticker through the service; ignores
        ``on_batch`` and brings up the unified async engine instead."""
        import warnings
        warnings.warn(
            "RLCService.start_ticker() is deprecated; use start() — "
            "the unified lifecycle runs the ticker and an execution "
            "thread for you", DeprecationWarning, stacklevel=2)
        self.start(tick_interval_s=interval_s
                   if interval_s is not None else 0.002)

    def stop_ticker(self) -> None:
        """Deprecated: use :meth:`close` (or the context manager)."""
        import warnings
        warnings.warn(
            "RLCService.stop_ticker() is deprecated; use close()",
            DeprecationWarning, stacklevel=2)
        self.close()

    # -- observability --------------------------------------------------- #
    def audit_report(self, sample: int = 128, seed: int = 0) -> dict:
        """Walk the serving index and return a ``repro.obs.audit/1``
        health report (entry histograms, redundancy/soundness probes,
        byte accounting, drift fingerprint). The report is kept for the
        next :meth:`telemetry_snapshot` and its headline numbers are
        banked as ``rlc_audit_*`` gauges."""
        from repro.obs.audit import audit_index, bank_audit_metrics
        rep = audit_index(self.frozen, self._id_to_mr, index=self.index,
                          graph=self.graph,
                          device_index=self.device_index,
                          sample=sample, seed=seed)
        self._last_audit = rep
        bank_audit_metrics(self.obs.registry, rep)
        return rep

    def drain_shadow(self) -> int:
        """Run every pending shadow check now (foreground); returns the
        number checked. No-op (0) when shadow verification is off."""
        return self._shadow.drain() if self._shadow is not None else 0

    def telemetry_snapshot(self, extra: Optional[dict] = None) -> dict:
        """Versioned registry+tracer snapshot (``repro.obs.export``)."""
        ex = dict(extra) if extra else {}
        ex.setdefault("queries_served", self.queries_served)
        ex.setdefault("deltas_applied", self.deltas_applied)
        if self._shadow is not None:
            self._shadow.drain()
            ex.setdefault("shadow", self._shadow.stats())
        if self._last_audit is not None:
            ex.setdefault("audit", self._last_audit)
        return self.obs.snapshot(extra=ex)

    def chrome_trace(self) -> dict:
        """Recorded spans as a Chrome ``trace_event`` JSON object."""
        return self.obs.chrome_trace()

    def prometheus(self) -> str:
        """The registry in Prometheus text exposition format."""
        return self.obs.prometheus()

    def stats(self) -> dict:
        """Versioned observability snapshot (``repro.service.stats/1``,
        the bench-JSON shape; see :mod:`repro.service.stats`).

        Every subsystem is one sub-dict — ``executor`` holds both the
        per-backend latency summaries and the fallback count. The cache
        section's ``hit_rate`` is a ratio in [0, 1]. Shared sections
        come from :func:`repro.service.stats.base_stats`; validate with
        :func:`repro.service.stats.validate_stats`.
        """
        from .stats import base_stats
        out = base_stats(self, "single", "local")
        out.update(
            executor=dict(
                backends=self.executor.stats(),
                fallbacks=self.executor.fallbacks),
            index=dict(
                entries=self.index.num_entries(),
                size_bytes=self.index.size_bytes(),
                num_mrs=len(self.mr_ids),
                device=self.device_index is not None,
                row_len=(self.device_index.row_len
                         if self.device_index else None)),
        )
        return out
