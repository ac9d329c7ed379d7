"""Admission of a ``query_batch`` call in one pass: each call is checked
and resolved before anything is queued, each distinct constraint once,
and the cache and scheduler bookkeeping is cheaper per query. These tests
hold the pass to the per-query loop it replaced: the same answers,
dispositions, order and counter totals, and the same errors."""
import re

import numpy as np
import pytest

from repro.core.index_builder import build_rlc_index
from repro.graphgen import erdos_renyi
from repro.service import (SHED, Answer, ExpressionError, RLCService,
                           ServiceConfig)
from repro.service.executor import PendingBatch

N = 60          # vertices of the test graph
MRS = [(0,), (1,), (2,), (0, 1), (0, 2), (1, 0), (1, 2), (2, 0), (2, 1)]


@pytest.fixture(scope="module")
def graph():
    g = erdos_renyi(N, 3.0, 3, seed=31)
    return g, build_rlc_index(g, 2)


@pytest.fixture(autouse=True)
def answered_at_flush(monkeypatch):
    """Every dispatched batch is answered at its flush, as the per-query
    loop answers it, so repeats of a key meet the same cache."""
    monkeypatch.setattr(PendingBatch, "done", lambda self: True)


def per_query(svc, queries):
    """The admission loop one query at a time, as the facade ran it before
    calls were admitted in one pass: parse, sketch, cache probe, admission
    decision and submit per query, each flushed batch run to the end."""
    answers, slot = [None] * len(queries), {}
    admission = svc.ctl.admission
    for i, (s, t, c) in enumerate(queries):
        s, t, mr_id, mr_len = svc._admit(s, t, c)
        key = (s, t, mr_id)
        svc.ctl.observe_admit(key, mr_len)
        hit = svc.cache.get(key, mr_len=mr_len)
        if hit is not None:
            answers[i] = Answer(hit, "cache_hit")
            continue
        if admission is not None:
            decision, victim = admission.decide(key, mr_len, svc.batcher)
            if decision == "shed":
                answers[i] = SHED
                continue
            if decision == "evict" and svc.batcher.evict(victim):
                for pos in slot.pop(victim.req_id, ()):
                    answers[pos] = SHED
        req, ready = svc.batcher.submit(s, t, mr_id, mr_len)
        slot.setdefault(req.req_id, []).append(i)
        for batch in ready:
            svc._execute(batch, answers, slot)
    for batch in svc.batcher.drain():
        svc._execute(batch, answers, slot)
    return answers


def shape(answers):
    return [("shed",) if a is SHED else (a.value, a.disposition, a.backend)
            for a in answers]


def counts(svc):
    """Every counter total the admission pass feeds."""
    reg, st = svc.obs.registry, svc.cache.stats

    def series(name):
        m = reg.get(name)
        return {} if m is None else {k: c.value for k, c in m.series()}
    wait = reg.get("rlc_batcher_queue_wait_seconds")
    return dict(
        lookups=series("rlc_cache_lookups"),
        mr_lookups=series("rlc_cache_mr_lookups"),
        stats=(st.hits, st.misses, st.expirations, dict(st.by_mr_len)),
        coalesced=series("rlc_batcher_coalesced"),
        batches=series("rlc_batcher_batches"),
        admission=series("rlc_admission_requests"),
        shed=series("rlc_admission_shed"),
        queue_wait=sum(c.reservoir.count for _k, c in wait.series()))


def _keys(rng, n, pool):
    """``n`` (s, t, mr) drawn from ``pool`` distinct ones: repeats land in
    one batch and in the batches after it."""
    keys = [(int(rng.integers(N)), int(rng.integers(N)),
             MRS[int(rng.integers(len(MRS)))]) for _ in range(pool)]
    return [keys[int(i)] for i in rng.integers(pool, size=n)]


def _spellings(svc, rng, n):
    """One MR per query, written as a string (also as a power of the MR),
    a tuple, a :class:`PathExpression` or a list."""
    out = []
    for i in range(n):
        s, t, mr = (int(rng.integers(N)), int(rng.integers(N)),
                    MRS[int(rng.integers(len(MRS)))])
        body = " ".join(map(str, mr))
        c = [f"({body})+", mr, svc.parse(f"({body})+"), list(mr),
             f"({body} {body})+"][i % 5]
        out.append((s, t, c))
    return out


CALLS = {
    "one": lambda svc, rng: _keys(rng, 1, 1),
    "repeats": lambda svc, rng: _keys(rng, 90, 24),
    "spellings": lambda svc, rng: _spellings(svc, rng, 60),
    # 9 constraints against a batch of 4: more MRs than a bucket holds
    "many-mrs": lambda svc, rng: [(int(rng.integers(N)),
                                   int(rng.integers(N)), MRS[i % 9])
                                  for i in range(45)],
}


def _service(graph, backend, **cfg):
    g, index = graph
    cfg = dict(dict(k=2, batch_size=4, max_wait_ms=1e6, backend=backend),
               **cfg)
    return RLCService.build(g, ServiceConfig(**cfg), index=index)


@pytest.mark.parametrize("call", sorted(CALLS))
@pytest.mark.parametrize("backend", ["numpy", "sorted"])
def test_call_admitted_as_per_query(graph, backend, call):
    bulk, ref, one = (_service(graph, backend) for _ in range(3))
    queries = CALLS[call](bulk, np.random.default_rng(7))
    # twice: the second call of the same queries hits the cache
    calls = []
    for _ in range(2):
        got, want = bulk.query_batch(queries), per_query(ref, queries)
        assert shape(got) == shape(want)
        assert counts(bulk) == counts(ref)
        calls.append(got)
    # one lookup per query, alike in every series
    c = counts(bulk)
    hits, misses, expired, by_len = c["stats"]
    assert (hits, misses, expired) == tuple(
        c["lookups"].get((o,), 0) for o in ("hit", "miss", "expired"))
    assert hits + misses == 2 * len(queries) == sum(c["lookups"].values())
    assert by_len == {
        int(ln): (c["mr_lookups"].get(("hit", ln), 0),
                  c["mr_lookups"].get(("miss", ln), 0))
        for _o, ln in c["mr_lookups"]}
    # a query at a time: the same values in the same order, and where no
    # query repeats in the call (nothing to coalesce) the same dispositions
    singles = [one.query(*q) for q in queries]
    assert [a.value for a in calls[0]] == [a.value for a in singles]
    if len(set(ref._admit_call(queries)[0])) == len(queries):
        assert shape(calls[0]) == shape(singles)


def test_deadline_flushes_as_per_query(graph):
    """A deadline of 0: every admission flushes its own bucket on the
    deadline, in the pass as in the per-query loop."""
    bulk, ref = (_service(graph, "numpy", max_wait_ms=0.0)
                 for _ in range(2))
    queries = _keys(np.random.default_rng(3), 60, 20)
    assert shape(bulk.query_batch(queries)) == shape(per_query(ref, queries))
    assert counts(bulk) == counts(ref)
    b = bulk.batcher
    assert b.batches_deadline == ref.batcher.batches_deadline > 0
    assert b.batches_full == b.batches_drain == 0


def test_expired_entries_counted_as_per_query(graph):
    """Entries past their TTL count as expiries, not hits or misses."""
    bulk, ref = (_service(graph, "numpy", cache_ttl_s=1.0)
                 for _ in range(2))
    clock = [0.0]
    for svc in (bulk, ref):
        svc.cache.clock = lambda: clock[0]
    queries = _keys(np.random.default_rng(9), 40, 30)
    for now in (0.0, 0.5, 2.0):
        clock[0] = now
        assert shape(bulk.query_batch(queries)) == shape(
            per_query(ref, queries))
        assert counts(bulk) == counts(ref)
    assert bulk.cache.stats.expirations > 0 and bulk.cache.stats.hits > 0


def test_sharded_call_admitted_as_per_query(graph):
    from repro.service.sharded import ShardedRLCService, ShardedServiceConfig
    g, index = graph

    def sharded():
        return ShardedRLCService.build(g, ShardedServiceConfig(
            k=2, batch_size=4, max_wait_ms=1e6, num_shards=2), index=index)
    bulk, ref = sharded(), sharded()
    single = _service(graph, "numpy")
    queries = CALLS["repeats"](bulk, np.random.default_rng(11))
    for _ in range(2):
        got = bulk.query_batch(queries)
        assert shape(got) == shape(per_query(ref, queries))
        assert counts(bulk) == counts(ref)
        assert got == single.query_batch(queries)


def test_admission_control_sheds_as_per_query(graph):
    """Admission control decides per miss, in order, against the
    scheduler as it stands: the same SHED and evict answers."""
    cfg = dict(cache_capacity=0, batch_size=8, admission_max_pending=4,
               clock=lambda: 0.0)
    bulk, ref = (_service(graph, "numpy", **cfg) for _ in range(2))
    queries = _keys(np.random.default_rng(5), 80, 12)
    got, want = bulk.query_batch(queries), per_query(ref, queries)
    assert shape(got) == shape(want)
    assert counts(bulk) == counts(ref)
    reg = bulk.obs.registry.get("rlc_admission_shed")
    assert reg.value(reason="queue_full") > 0
    assert reg.value(reason="evicted") > 0
    assert any(a is SHED for a in got)


BAD = {
    "s-range": (N, 0, (0, 1)),
    "t-negative": (0, -1, "(0 1)+"),
    "unknown-label": (0, 1, "(frob)+"),
    "label-range": (0, 1, (0, 7)),
    "over-k": (0, 1, [0, 1, 2]),
    "no-plus": (0, 1, "(0 1)"),
}


@pytest.mark.parametrize("bad", sorted(BAD))
def test_bad_query_raises_before_anything_is_queued(graph, bad):
    svc = _service(graph, "numpy")
    with pytest.raises((ValueError, ExpressionError)) as want:
        svc._admit(*BAD[bad])
    good = _keys(np.random.default_rng(1), 9, 9)
    before = counts(svc)
    with pytest.raises(want.type, match=re.escape(str(want.value))):
        svc.query_batch(good + [BAD[bad]] + good)
    assert svc.batcher.pending() == 0
    assert svc.queries_served == 0
    assert counts(svc) == before
    # the service serves the next call
    assert svc.query_batch(good) == per_query(_service(graph, "numpy"), good)


def test_call_that_fails_midway_counts_its_lookups(graph, monkeypatch):
    """A call that raises after some admissions counts the cache
    lookups it made, and no others."""
    svc = _service(graph, "numpy")
    submit, calls = svc.batcher.submit, []

    def failing(*a):
        calls.append(a)
        if len(calls) == 5:
            raise RuntimeError("scheduler lost")
        return submit(*a)
    monkeypatch.setattr(svc.batcher, "submit", failing)
    queries = _keys(np.random.default_rng(4), 30, 30)
    with pytest.raises(RuntimeError, match="scheduler lost"):
        svc.query_batch(queries)
    assert svc.cache.stats.misses == 5
    assert sum(counts(svc)["mr_lookups"].values()) == 5


def _parsed(svc):
    return svc.obs.registry.get("rlc_admit_parsed").value()


def test_admit_parsed_counts_each_distinct_constraint_once(graph):
    svc = _service(graph, "numpy")
    rng = np.random.default_rng(2)
    queries = [(int(rng.integers(N)), int(rng.integers(N)),
                MRS[int(rng.integers(len(MRS)))]) for _ in range(300)]
    assert len({c for _s, _t, c in queries}) == 9
    svc.query_batch(queries)
    assert _parsed(svc) == 9
    # an unhashable constraint is parsed each time it appears
    lists = [(1, 2, [0, 1]), (3, 4, [0, 1]), (5, 6, [2])]
    svc.query_batch(queries + lists)
    assert _parsed(svc) == 9 + 9 + 3
    # equal spellings of one MR are distinct constraints
    svc.query_batch([(1, 2, "(0 1)+"), (1, 2, (0, 1)), (1, 2, "(0 1)+")])
    assert _parsed(svc) == 21 + 2
