"""Unit tests for the online serving subsystem (repro.service.*):
expression parser, LRU result cache, micro-batching scheduler, multi-
backend executor and the RLCService facade."""
import numpy as np
import pytest

from repro.core.baselines import bibfs_rlc
from repro.core.index_builder import build_rlc_index
from repro.core.minimum_repeat import enumerate_mrs
from repro.core.queries import biased_true_queries
from repro.graphgen import erdos_renyi, fig1_graph
from repro.service import (BatchExecutor, ExecutorError, ExpressionError,
                           MicroBatcher, RLCService, ResultCache,
                           ServiceConfig, parse_expression)
from repro.service.executor import PendingBatch


# ------------------------------------------------------------------ #
# Parser
# ------------------------------------------------------------------ #
def test_parse_numeric_forms():
    for text in ["(0 1)+", "( 0 1 )+", '("0 1")+', "'0 1'+", "0,1+",
                 "(0, 1)+"]:
        e = parse_expression(text, num_labels=3, k=2)
        assert e.mr == (0, 1), text


def test_parse_named_labels():
    names = {"debits": 2, "credits": 3}
    e = parse_expression("(debits credits)+", num_labels=5, k=2,
                         label_names=names)
    assert e.labels == (2, 3)
    assert e.mr == (2, 3)


def test_parse_canonicalizes_to_minimum_repeat():
    # (a b a b)+ and (a b)+ denote the same query (Lemma 1)
    e = parse_expression("(0 1 0 1)+", num_labels=2, k=2)
    assert e.labels == (0, 1, 0, 1)
    assert e.mr == (0, 1)
    # and a long power of a short MR is accepted even when |labels| > k
    e = parse_expression("(1 1 1 1 1)+", num_labels=2, k=2)
    assert e.mr == (1,)


@pytest.mark.parametrize("bad", [
    "",                 # empty
    "   ",              # blank
    "(0 1)",            # missing +
    "0 1",              # missing +
    "()+",              # empty group
    "(0 1+",            # unbalanced parens
    '("0 1)+',          # unbalanced quote
    "((0 1))+",         # nested group
    "(0+ 1)+",          # stray +
    "(7)+",             # label id out of alphabet (num_labels=3)
    "(-1)+",            # negative id never parses as a label token
    "(frob)+",          # unknown name
    "(0 1 2)+",         # |MR| = 3 > k = 2
])
def test_parse_rejects_malformed(bad):
    with pytest.raises(ExpressionError):
        parse_expression(bad, num_labels=3, k=2)


def test_parse_error_messages_are_actionable():
    with pytest.raises(ExpressionError, match="unknown label 'frob'"):
        parse_expression("(frob)+", num_labels=3, k=2,
                         label_names={"knows": 0})
    with pytest.raises(ExpressionError, match="> k=2"):
        parse_expression("(0 1 2)+", num_labels=3, k=2)
    with pytest.raises(ExpressionError, match="out of range"):
        parse_expression("(5)+", num_labels=3, k=2)


# ------------------------------------------------------------------ #
# LRU result cache
# ------------------------------------------------------------------ #
def test_cache_hit_returns_identical_answer():
    c = ResultCache(capacity=8)
    c.put((1, 2, 0), True)
    c.put((3, 4, 1), False)
    assert c.get((1, 2, 0)) is True
    assert c.get((3, 4, 1)) is False    # negative answers are cached too
    assert c.stats.hits == 2 and c.stats.misses == 0


def test_cache_miss_and_eviction_at_capacity():
    c = ResultCache(capacity=2)
    c.put((0, 0, 0), True)
    c.put((1, 1, 1), True)
    assert c.get((9, 9, 9)) is None
    c.put((2, 2, 2), True)              # evicts LRU (0,0,0)
    assert len(c) == 2
    assert c.stats.evictions == 1
    assert c.get((0, 0, 0)) is None
    assert c.get((2, 2, 2)) is True


def test_cache_lru_recency_order():
    c = ResultCache(capacity=2)
    c.put((0, 0, 0), True)
    c.put((1, 1, 1), False)
    assert c.get((0, 0, 0)) is True     # refresh (0,0,0)
    c.put((2, 2, 2), True)              # now (1,1,1) is LRU -> evicted
    assert c.get((1, 1, 1)) is None
    assert c.get((0, 0, 0)) is True


def test_cache_zero_capacity_disables():
    c = ResultCache(capacity=0)
    c.put((0, 0, 0), True)
    assert c.get((0, 0, 0)) is None
    assert len(c) == 0


# ------------------------------------------------------------------ #
# Micro-batching scheduler
# ------------------------------------------------------------------ #
def test_scheduler_flushes_on_batch_full():
    clock = [0.0]
    b = MicroBatcher(batch_size=4, max_wait_s=100.0, clock=lambda: clock[0])
    for i in range(3):
        _, ready = b.submit(i, i, 0, 1)
        assert ready == []
    _, ready = b.submit(3, 3, 0, 1)
    assert len(ready) == 1
    batch = ready[0]
    assert batch.reason == "full"
    assert batch.n_real == 4 and batch.n_padding == 0
    assert [r.s for r in batch.requests] == [0, 1, 2, 3]
    assert b.pending() == 0


def test_scheduler_flushes_on_deadline():
    clock = [0.0]
    b = MicroBatcher(batch_size=8, max_wait_s=0.5, clock=lambda: clock[0])
    b.submit(0, 1, 0, 1)
    assert b.poll() == []               # deadline not reached
    clock[0] = 0.6
    ready = b.poll()
    assert len(ready) == 1
    assert ready[0].reason == "deadline"
    assert ready[0].n_real == 1
    # underfull flushes carry real slots only — no repeated-request
    # padding (the executor pads jit backends internally)
    assert len(ready[0].s) == 1 and ready[0].n_padding == 0
    assert list(ready[0].s) == [0] and list(ready[0].t) == [1]


def test_scheduler_deadline_checked_on_submit():
    clock = [0.0]
    b = MicroBatcher(batch_size=8, max_wait_s=0.5, clock=lambda: clock[0])
    b.submit(0, 1, 0, 1)                # bucket |MR|=1
    clock[0] = 1.0
    _, ready = b.submit(2, 3, 4, 2)     # bucket |MR|=2; poll fires bucket 1
    assert len(ready) == 1
    assert ready[0].mr_len == 1 and ready[0].reason == "deadline"
    assert b.pending() == 1             # the |MR|=2 request still queued


def test_scheduler_coalesces_duplicate_inflight_keys():
    clock = [0.0]
    b = MicroBatcher(batch_size=4, max_wait_s=100.0, clock=lambda: clock[0])
    r1, _ = b.submit(7, 9, 2, 1)
    r2, _ = b.submit(7, 9, 2, 1)        # duplicate while in flight
    assert r2.req_id == r1.req_id       # same request, no second slot
    assert b.pending() == 1 and b.coalesced == 1
    # a different key still takes its own slot
    r3, _ = b.submit(7, 9, 3, 1)
    assert r3.req_id != r1.req_id and b.pending() == 2
    # after the flush the key is no longer in flight -> fresh request
    batches = b.drain()
    assert len(batches) == 1 and batches[0].n_real == 2
    r4, _ = b.submit(7, 9, 2, 1)
    assert r4.req_id != r1.req_id
    assert b.coalesced == 1


def test_scheduler_coalesced_batch_never_double_books():
    b = MicroBatcher(batch_size=2, max_wait_s=100.0, clock=lambda: 0.0)
    b.submit(0, 1, 0, 1)
    _, ready = b.submit(0, 1, 0, 1)     # coalesced: bucket must NOT fill
    assert ready == []
    _, ready = b.submit(2, 3, 0, 1)     # second distinct request fills it
    assert len(ready) == 1
    assert [r.s for r in ready[0].requests] == [0, 2]


def test_service_fans_coalesced_answers_out():
    g = erdos_renyi(40, 3.0, 3, seed=17)
    svc = RLCService.build(g, ServiceConfig(k=2, batch_size=32,
                                            cache_capacity=0))
    # duplicates within one query_batch; cache off, so only coalescing
    # can collapse them
    qs = [(1, 2, "(0 1)+"), (3, 4, "(0)+"), (1, 2, "(0 1)+"),
          (1, 2, "(0 1)+"), (3, 4, "(0)+")]
    got = svc.query_batch(qs)
    assert got[0] == got[2] == got[3]
    assert got[1] == got[4]
    assert got == [bibfs_rlc(g, s, t,
                             parse_expression(c, num_labels=3, k=2).mr)
                   for s, t, c in qs]
    st = svc.stats()["scheduler"]
    assert st["coalesced"] == 3


def test_scheduler_background_ticker_fires_deadline_flush():
    import threading
    b = MicroBatcher(batch_size=8, max_wait_s=0.02)
    flushed = []
    done = threading.Event()

    def on_batch(batch):
        flushed.append(batch)
        done.set()

    assert not b.ticker_running
    b.start_ticker(on_batch)
    try:
        b.submit(0, 1, 0, 1)
        # no further admissions: only the ticker can flush this bucket
        assert done.wait(timeout=5.0), "ticker never flushed"
    finally:
        b.stop_ticker()
    assert not b.ticker_running
    assert len(flushed) == 1
    assert flushed[0].reason == "deadline" and flushed[0].n_real == 1
    assert b.pending() == 0
    with pytest.raises(RuntimeError):   # double start is a bug
        b.start_ticker(on_batch)
        b.start_ticker(on_batch)
    b.stop_ticker()


def test_scheduler_ticker_survives_callback_errors():
    import threading
    b = MicroBatcher(batch_size=8, max_wait_s=0.01)
    seen = []
    ok = threading.Event()

    def flaky(batch):
        if not seen:
            seen.append("boom")
            raise RuntimeError("executor died")
        ok.set()

    b.start_ticker(flaky)
    try:
        b.submit(0, 1, 0, 1)            # first flush: callback raises
        deadline = 5.0
        import time as _t
        t0 = _t.monotonic()
        while not seen and _t.monotonic() - t0 < deadline:
            _t.sleep(0.005)
        b.submit(2, 3, 0, 1)            # second flush must still fire
        assert ok.wait(timeout=5.0), "ticker died after callback error"
    finally:
        b.stop_ticker()
    assert b.ticker_errors == 1


def test_scheduler_buckets_by_mr_length():
    b = MicroBatcher(batch_size=2, max_wait_s=100.0, clock=lambda: 0.0)
    _, r1 = b.submit(0, 0, 0, 1)
    _, r2 = b.submit(1, 1, 5, 2)        # different bucket: no flush yet
    assert r1 == [] and r2 == []
    _, r3 = b.submit(2, 2, 6, 2)        # fills the |MR|=2 bucket
    assert len(r3) == 1 and r3[0].mr_len == 2
    assert all(req.mr_len == 2 for req in r3[0].requests)
    drained = b.drain()
    assert len(drained) == 1 and drained[0].mr_len == 1


# ------------------------------------------------------------------ #
# Multi-backend executor
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def small_setup():
    g = erdos_renyi(40, 3.0, 3, seed=2)
    svc = RLCService.build(g, ServiceConfig(k=2, batch_size=8,
                                            cache_capacity=0))
    rng = np.random.default_rng(1)
    mrs = enumerate_mrs(3, 2)
    queries = [(int(rng.integers(40)), int(rng.integers(40)),
                mrs[int(rng.integers(len(mrs)))]) for _ in range(48)]
    return g, svc, queries


def test_executor_backends_agree(small_setup):
    g, svc, queries = small_setup
    ex = svc.executor
    s = np.array([q[0] for q in queries], np.int32)
    t = np.array([q[1] for q in queries], np.int32)
    mr = np.array([svc.mr_ids[q[2]] for q in queries], np.int32)
    ref, b0 = ex.execute(s, t, mr, backend="python")
    assert b0 == "python"
    for backend in ("numpy", "sorted", "pallas"):
        got, b = ex.execute(s, t, mr, backend=backend)
        assert b == backend
        np.testing.assert_array_equal(got, ref, err_msg=backend)


def test_executor_fallback_when_device_missing(small_setup):
    g, svc, queries = small_setup
    ex = BatchExecutor(svc.index, svc.frozen, device_index=None,
                       id_to_mr=svc._id_to_mr, backend="auto")
    assert not ex.available("pallas") and not ex.available("sorted")
    s = np.array([q[0] for q in queries[:8]], np.int32)
    t = np.array([q[1] for q in queries[:8]], np.int32)
    mr = np.array([svc.mr_ids[q[2]] for q in queries[:8]], np.int32)
    got, backend = ex.execute(s, t, mr)
    assert backend in ("numpy", "python")
    ref, _ = ex.execute(s, t, mr, backend="python")
    np.testing.assert_array_equal(got, ref)


def test_executor_fallback_on_backend_failure(small_setup):
    """A device backend that fails raises; it never falls through to a
    host backend that would hide the fault."""
    g, svc, queries = small_setup

    class Boom:
        row_len = 8

        def join(self, *a, **kw):
            raise RuntimeError("device lost")

    ex = BatchExecutor(svc.index, svc.frozen, device_index=Boom(),
                       id_to_mr=svc._id_to_mr, backend="sorted")
    s = np.array([q[0] for q in queries[:4]], np.int32)
    t = np.array([q[1] for q in queries[:4]], np.int32)
    mr = np.array([svc.mr_ids[q[2]] for q in queries[:4]], np.int32)
    with pytest.raises(ExecutorError, match="'sorted' failed") as err:
        ex.execute(s, t, mr)
    assert isinstance(err.value.__cause__, RuntimeError)
    assert ex.fallbacks == 0
    assert "sorted" not in ex.stats() and "numpy" not in ex.stats()
    # the host backends still answer when asked for explicitly
    got, backend = ex.execute(s, t, mr, backend="numpy")
    ref, _ = ex.execute(s, t, mr, backend="python")
    assert backend == "numpy"
    np.testing.assert_array_equal(got, ref)


def test_device_layout_failure_raises_at_build(monkeypatch):
    """use_device=True with a device layout that cannot be built raises;
    the service never comes up serving from the host instead."""
    from repro.core.device_index import DeviceIndex

    def boom(*a, **kw):
        raise RuntimeError("no device memory")

    g = erdos_renyi(30, 3.0, 3, seed=2)
    monkeypatch.setattr(DeviceIndex, "from_frozen", boom)
    with pytest.raises(RuntimeError, match="no device memory"):
        RLCService.build(g, ServiceConfig(k=2))
    svc = RLCService.build(g, ServiceConfig(k=2, use_device=False))
    assert svc.device_index is None and svc.query(0, 1, "(0)+") in (
        True, False)


def test_executor_records_per_backend_metrics(small_setup):
    g, svc, queries = small_setup
    stats = svc.executor.stats()
    # the fixture ran batches through every backend above
    assert any(k in stats for k in ("python", "numpy", "sorted", "pallas"))
    for v in stats.values():
        assert v["batches"] >= 1
        assert v["p99_ms"] >= v["p50_ms"] >= 0.0


# ------------------------------------------------------------------ #
# RLCService facade
# ------------------------------------------------------------------ #
def test_service_end_to_end_matches_oracle():
    g = erdos_renyi(50, 3.0, 3, seed=5)
    svc = RLCService.build(g, ServiceConfig(k=2, batch_size=8,
                                            cache_capacity=256))
    rng = np.random.default_rng(7)
    mrs = enumerate_mrs(3, 2)
    queries, want = [], []
    for _ in range(64):
        s, t = int(rng.integers(50)), int(rng.integers(50))
        L = mrs[int(rng.integers(len(mrs)))]
        queries.append((s, t, L))
        want.append(bibfs_rlc(g, s, t, L))
    got = svc.query_batch(queries)
    assert got == want
    # replay: everything should now come from the cache, same answers
    before = svc.cache.stats.hits
    assert svc.query_batch(queries) == want
    assert svc.cache.stats.hits >= before + len(set(queries))


def test_service_accepts_string_and_named_constraints():
    g, names, labels = fig1_graph()
    svc = RLCService.build(
        g, ServiceConfig(k=3, batch_size=4, label_names=labels))
    assert svc.query(names["A14"], names["A19"], "(debits credits)+") == True  # noqa: E712 — Answer equality
    assert svc.query(names["P10"], names["P13"],
                     "(knows knows worksFor)+") == False  # noqa: E712
    assert svc.query(names["A14"], names["A19"], (2, 3)) == True  # noqa: E712


def test_service_rejects_bad_input():
    g = erdos_renyi(20, 2.0, 2, seed=0)
    svc = RLCService.build(g, ServiceConfig(k=2))
    with pytest.raises(ExpressionError):
        svc.query(0, 1, "(0 1 0)+")      # |MR|=3 > k
    with pytest.raises(ValueError):
        svc.query(0, 99, "(0)+")         # vertex out of range
    with pytest.raises(ValueError):
        RLCService.build(g, ServiceConfig(k=3),
                         index=build_rlc_index(g, 2))  # k mismatch


def test_service_stats_shape():
    g = erdos_renyi(30, 2.0, 2, seed=3)
    svc = RLCService.build(g, ServiceConfig(k=2, batch_size=4))
    svc.query_batch([(0, 1, "(0)+"), (1, 2, "(1)+"), (0, 1, "(0)+")])
    st = svc.stats()
    assert st["queries_served"] == 3
    assert st["cache"]["hits"] + st["cache"]["misses"] == 3
    assert 0.0 <= st["cache"]["hit_rate"] <= 1.0    # ratio, not percent
    assert st["index"]["num_mrs"] == len(svc.mr_ids)
    assert st["scheduler"]["pending"] == 0
    # executor observability is one nested dict: per-backend latencies AND
    # the fallback count together (no more flat `fallbacks` sibling)
    assert "fallbacks" not in st
    assert set(st["executor"]) == {"backends", "fallbacks"}
    assert st["executor"]["fallbacks"] >= 0
    for b in st["executor"]["backends"].values():
        assert b["p99_ms"] >= b["p50_ms"] >= 0.0


# ------------------------------------------------------------------ #
# Pipelined query_batch: dispatch at flush, answers read back later
# ------------------------------------------------------------------ #
class SyncService(RLCService):
    """The facade with ``_run_batch`` overridden, which keeps each batch
    on the synchronous path: run to the end when it flushes."""

    def _run_batch(self, batch, tr=None):
        return RLCService._run_batch(self, batch, tr)


@pytest.fixture(scope="module")
def pipe_graph():
    g = erdos_renyi(60, 3.0, 3, seed=31)
    return g, build_rlc_index(g, 2)


def _span_count(svc, name):
    series = svc.obs.registry.get("rlc_span_seconds")
    return sum(cell.reservoir.count for key, cell in series.series()
               if key[0] == name)


def _pool_queries(svc, n, pool=24, seed=0):
    """``n`` queries drawn from a pool of ``pool`` keys: repeats land in
    one batch and in batches after it."""
    rng = np.random.default_rng(seed)
    keys = [(int(rng.integers(60)), int(rng.integers(60)),
             svc._id_to_mr[int(rng.integers(len(svc._id_to_mr)))])
            for _ in range(pool)]
    return [keys[int(i)] for i in rng.integers(pool, size=n)]


@pytest.mark.parametrize("call, fill", [(1, 4), (45, 4), (90, 32)],
                         ids=["call1-fill4", "call45-fill4", "call90-fill32"])
@pytest.mark.parametrize("backend", ["pallas", "sorted", "numpy", "python"])
def test_pipelined_answers_as_synchronous(pipe_graph, backend, call, fill,
                                          monkeypatch):
    g, index = pipe_graph
    cfg = dict(k=2, batch_size=fill, max_wait_ms=1e6, cache_capacity=0,
               backend=backend)
    pipe = RLCService.build(g, ServiceConfig(**cfg), index=index)
    sync = SyncService.build(g, ServiceConfig(**cfg), index=index)
    queries = _pool_queries(pipe, call, seed=call)
    want = sync.query_batch(queries)
    # nothing reads back before the drain: every batch of the call is in
    # flight at once, repeats of one key among them
    monkeypatch.setattr(PendingBatch, "done", lambda self: False)
    got = pipe.query_batch(queries)
    assert [a.value for a in got] == [a.value for a in want]
    assert {a.backend for a in got} == {backend}
    assert got == [bibfs_rlc(g, s, t, L) for s, t, L in queries]
    by_key = {}
    for q, a in zip(queries, got):
        assert by_key.setdefault(q, a.value) == a.value
    batches = pipe.executor.recorders[backend].batches
    assert batches == sync.executor.recorders[backend].batches >= 1
    assert _span_count(pipe, "resolve") == batches
    assert _span_count(sync, "resolve") == 0
    assert _span_count(sync, "execute") == batches


def test_pipelined_answers_ready_batches_at_each_flush(pipe_graph,
                                                       monkeypatch):
    """A batch whose answers are back is answered at the next flush,
    before the drain; one whose answers are not waits for the drain."""
    g, index = pipe_graph
    svc = RLCService.build(g, ServiceConfig(
        k=2, batch_size=4, max_wait_ms=1e6, cache_capacity=0,
        backend="sorted"), index=index)
    at_drain = []
    drain = svc.batcher.drain

    def draining():
        at_drain.append((_span_count(svc, "execute"),
                         _span_count(svc, "resolve")))
        return drain()
    monkeypatch.setattr(svc.batcher, "drain", draining)
    monkeypatch.setattr(PendingBatch, "done", lambda self: True)
    svc.query_batch(_pool_queries(svc, 40, pool=40, seed=3))
    dispatched, answered = at_drain[-1]
    assert dispatched == answered > 0
    dispatched = answered = _span_count(svc, "resolve")
    monkeypatch.setattr(PendingBatch, "done", lambda self: False)
    svc.query_batch(_pool_queries(svc, 40, pool=40, seed=4))
    assert at_drain[-1][0] > dispatched and at_drain[-1][1] == answered
    assert _span_count(svc, "resolve") == _span_count(svc, "execute")


def test_lost_answers_raise_on_an_external_flush(pipe_graph, monkeypatch):
    """A batch taken out of the scheduler outside the call leaves holes;
    the call raises instead of answering them False."""
    g, index = pipe_graph
    for cls in (RLCService, SyncService):
        svc = cls.build(g, ServiceConfig(
            k=2, batch_size=4, max_wait_ms=1e6, cache_capacity=0,
            backend="sorted"), index=index)
        drain = svc.batcher.drain
        monkeypatch.setattr(svc.batcher, "drain", lambda: drain()[1:])
        queries = _pool_queries(svc, 10, pool=10, seed=5)
        with pytest.raises(RuntimeError, match="lost answers"):
            svc.query_batch(queries)


def test_sharded_service_stays_synchronous(pipe_graph):
    from repro.service.sharded import ShardedRLCService, ShardedServiceConfig
    g, index = pipe_graph
    svc = ShardedRLCService.build(g, ShardedServiceConfig(
        k=2, batch_size=4, max_wait_ms=1e6, cache_capacity=0,
        num_shards=2), index=index)
    base = RLCService.build(g, ServiceConfig(k=2), index=index)
    queries = _pool_queries(svc, 30, seed=6)
    assert svc.query_batch(queries) == base.query_batch(queries)
    assert _span_count(svc, "resolve") == 0
    # each batch runs to the end inside its `execute`, `answer` included
    assert _span_count(svc, "execute") == _span_count(svc, "answer") > 0


def test_failed_readback_in_a_call_raises(pipe_graph):
    """A join whose readback fails raises out of the call that dispatched
    it, as a failed dispatch does; the service serves the next call."""
    g, index = pipe_graph
    svc = RLCService.build(g, ServiceConfig(
        k=2, batch_size=4, max_wait_ms=1e6, cache_capacity=0,
        backend="sorted"), index=index)
    queries = _pool_queries(svc, 12, seed=7)
    want = svc.query_batch(queries)
    good = svc.executor.device_index
    svc.executor.device_index = _LostReadback()
    with pytest.raises(ExecutorError, match="'sorted' failed") as err:
        svc.query_batch(queries)
    assert isinstance(err.value.__cause__, RuntimeError)
    assert svc.executor.fallbacks == 0
    svc.executor.device_index = good
    assert svc.query_batch(queries) == want


class _Unready:
    """A join result whose readback fails."""

    def copy_to_host_async(self):
        pass

    def is_ready(self):
        return False

    def block_until_ready(self):
        raise RuntimeError("device lost")


class _LostReadback:
    """A device layout whose joins dispatch and never read back."""

    row_len = 8

    def join(self, q, method):
        return _Unready()


# ------------------------------------------------------------------ #
# biased_true_queries fix
# ------------------------------------------------------------------ #
def test_biased_true_queries_multi_label_and_false_side():
    g = erdos_renyi(60, 4.0, 3, seed=9)
    k = 3
    qs = biased_true_queries(g, k, n=80, seed=4)
    assert len(qs.true_queries) == 80
    assert len(qs.false_queries) > 0
    # the old bug: only ever single-label constraints
    lens = {len(L) for _, _, L in qs.true_queries}
    assert lens - {1}, f"expected multi-label MRs, got lengths {lens}"
    assert all(1 <= len(L) <= k for _, _, L in qs.true_queries)
    # verify both sides against the oracle
    for s, t, L in qs.true_queries[:40]:
        assert bibfs_rlc(g, s, t, L), (s, t, L)
    for s, t, L in qs.false_queries[:40]:
        assert not bibfs_rlc(g, s, t, L), (s, t, L)
