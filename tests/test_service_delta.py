"""Serving-layer delta integration: ``apply_delta`` end-to-end, targeted
cache invalidation (delta + hot-swap), cache TTL, and the idempotent
``close()`` / context-manager shutdown that stops the deadline ticker."""
import threading
import time

import numpy as np
import pytest

from repro.build import GraphDelta, build_rlc_index
from repro.core.baselines import bibfs_rlc
from repro.core.minimum_repeat import enumerate_mrs
from repro.core.queries import biased_true_queries
from repro.graphgen import barabasi_albert, erdos_renyi, random_delta
from repro.service import RLCService, ServiceConfig
from repro.service.cache import ResultCache
from repro.service.sharded import ShardedRLCService, ShardedServiceConfig


def reference_answers(g, k, queries):
    ref = build_rlc_index(g, k, backend="python")
    return [ref.query(s, t, mr) for s, t, mr in queries]


def sample_queries(g, k, rng, n_per_mr=4):
    return [(int(rng.integers(g.num_vertices)),
             int(rng.integers(g.num_vertices)), mr)
            for mr in enumerate_mrs(g.num_labels, k)
            for _ in range(n_per_mr)]


# ------------------------------------------------------------------ #
# RLCService.apply_delta
# ------------------------------------------------------------------ #
def test_service_apply_delta_answers():
    g = erdos_renyi(120, 2.2, 3, seed=41)
    svc = RLCService.build(g, ServiceConfig(
        k=2, use_device=False, build_backend="numpy",
        delta_fallback_frac=1.0))
    rng = np.random.default_rng(42)
    for step in range(3):
        delta = random_delta(svc.graph, 2, 2, rng)
        summary = svc.apply_delta(delta)
        assert summary["deltas_applied"] == step + 1
        queries = sample_queries(svc.graph, 2, rng)
        got = svc.query_batch(queries)
        want = reference_answers(svc.graph, 2, queries)
        assert got == want
    assert svc.stats()["deltas_applied"] == 3
    assert svc.stats()["build"]["backend"].startswith("delta[")


def oracle_queries(g, k, seed, extra=()):
    """A true/false mix on ``g`` (true ones from walks) and the BiBFS
    oracle's answers to it."""
    qs = biased_true_queries(g, k, n=12, seed=seed)
    queries = qs.true_queries + qs.false_queries + list(extra)
    return queries, [bibfs_rlc(g, s, t, L) for s, t, L in queries]


DELTA_GRAPHS = {
    "er": lambda: erdos_renyi(60, 2.2, 3, seed=44),
    "ba": lambda: barabasi_albert(60, 2, 3, seed=45),
}


@pytest.mark.parametrize("backend", ["pallas", "sorted", "numpy", "python"])
@pytest.mark.parametrize("graph", sorted(DELTA_GRAPHS))
def test_service_delta_chain_per_backend(graph, backend):
    """A chain of insert+delete deltas served by one backend: after each
    one, every answer is the oracle's on the mutated graph."""
    g = DELTA_GRAPHS[graph]()
    svc = RLCService.build(g, ServiceConfig(
        k=2, batch_size=16, backend=backend, delta_fallback_frac=1.0))
    rng = np.random.default_rng(46)
    for step in range(3):
        svc.apply_delta(random_delta(svc.graph, 2, 2, rng))
        queries, want = oracle_queries(svc.graph, 2, seed=step)
        got = svc.query_batch(queries)
        assert [a.value for a in got] == want, step
        assert {a.backend for a in got} <= {backend, None}
    assert svc.executor.recorders[backend].batches >= 3
    assert svc.stats()["deltas_applied"] == 3


@pytest.mark.parametrize("k", [2, 3])
@pytest.mark.parametrize("backend", ["pallas", "sorted"])
@pytest.mark.parametrize("graph", ["er", "ba"])
def test_delta_lengthening_longest_row_grows_device_rows(graph, backend, k):
    """A write that makes the longest index row longer than the device
    rows: the layout is re-padded to hold it, no entry is cut, and the
    device join still answers as the oracle does."""
    g = (erdos_renyi(40, 1.0, 3, seed=41) if graph == "er"
         else barabasi_albert(40, 1, 3, seed=42))
    svc = RLCService.build(g, ServiceConfig(
        k=k, batch_size=16, cache_capacity=0, backend=backend,
        delta_fallback_frac=1.0))
    queries, want = oracle_queries(g, k, seed=0)
    assert [a.value for a in svc.query_batch(queries)] == want
    row_len0 = svc.device_index.row_len
    svc.apply_delta(random_delta(svc.graph, 40, 0,
                                 np.random.default_rng(0)))
    fz = svc.frozen
    assert fz.max_row > row_len0
    assert svc.device_index.row_len >= fz.max_row
    assert svc.stats()["index"]["row_len"] == svc.device_index.row_len
    # every query out of the longest out-row and into the longest in-row
    s_long = int(np.argmax(np.diff(fz.out_indptr)))
    t_long = int(np.argmax(np.diff(fz.in_indptr)))
    mrs = enumerate_mrs(g.num_labels, k)
    extra = [(s_long, v, mrs[v % len(mrs)]) for v in range(40)] + \
        [(v, t_long, mrs[v % len(mrs)]) for v in range(40)]
    queries, want = oracle_queries(svc.graph, k, seed=1, extra=extra)
    got = svc.query_batch(queries)
    assert [a.value for a in got] == want
    assert {a.backend for a in got} == {backend}


def test_service_apply_delta_invalid_delta_raises():
    g = erdos_renyi(50, 2.0, 3, seed=43)
    svc = RLCService.build(g, ServiceConfig(k=2, use_device=False))
    e0 = g.edges[0].tolist()
    with pytest.raises(ValueError):
        svc.apply_delta(GraphDelta.of([e0], []))   # edge already present
    assert svc.deltas_applied == 0


def test_service_delta_targeted_cache_invalidation():
    """Stale keys are evicted; keys whose (s, t) rows stayed clean keep
    serving from cache."""
    g = erdos_renyi(150, 2.0, 3, seed=44)
    svc = RLCService.build(g, ServiceConfig(
        k=2, use_device=False, build_backend="numpy",
        delta_fallback_frac=1.0, cache_capacity=4096))
    rng = np.random.default_rng(45)
    queries = sample_queries(svc.graph, 2, rng, n_per_mr=8)
    svc.query_batch(queries)                    # prime the cache
    primed = set(svc.cache._d)
    assert primed
    delta = random_delta(svc.graph, 1, 1, rng)
    summary = svc.apply_delta(delta)
    dirty_s = set(summary["dirty_out"])
    dirty_t = set(summary["dirty_in"])
    survivors = set(svc.cache._d)
    # every evicted key was dirty; every surviving key was not
    for (s, t, mr) in primed - survivors:
        assert s in dirty_s or t in dirty_t
    for (s, t, mr) in survivors:
        assert s not in dirty_s and t not in dirty_t
    assert summary["cache_evicted"] == len(primed - survivors)
    assert svc.cache.stats.invalidations == summary["cache_evicted"]
    # survivors still serve (and answers post-delta are correct)
    got = svc.query_batch(queries)
    assert got == reference_answers(svc.graph, 2, queries)


# ------------------------------------------------------------------ #
# Cache TTL
# ------------------------------------------------------------------ #
def test_cache_ttl_expiry_with_fake_clock():
    now = [0.0]
    c = ResultCache(16, ttl_s=5.0, clock=lambda: now[0])
    c.put((1, 2, 0), True)
    assert c.get((1, 2, 0)) is True
    now[0] = 4.9
    assert c.get((1, 2, 0)) is True             # still fresh
    now[0] = 10.0
    assert c.get((1, 2, 0)) is None             # expired -> miss + evict
    assert c.stats.expirations == 1
    assert len(c) == 0
    with pytest.raises(ValueError):
        ResultCache(16, ttl_s=0.0)


def test_cache_invalidate_rows_unit():
    c = ResultCache(16)
    c.put((1, 2, 0), True)
    c.put((3, 4, 0), False)
    c.put((5, 2, 1), True)
    n = c.invalidate_rows(dirty_s={1}, dirty_t={4})
    assert n == 2
    assert c.get((5, 2, 1)) is True
    assert c.stats.invalidations == 2


def test_service_config_ttl_plumbed():
    g = erdos_renyi(40, 2.0, 2, seed=46)
    svc = RLCService.build(g, ServiceConfig(k=2, use_device=False,
                                            cache_ttl_s=123.0))
    assert svc.cache.ttl_s == 123.0
    sh = ShardedRLCService.build(
        g, ShardedServiceConfig(k=2, use_device=False, num_shards=2,
                                cache_ttl_s=45.0))
    assert sh.cache.ttl_s == 45.0


# ------------------------------------------------------------------ #
# ShardedRLCService.apply_delta + hot_swap invalidation
# ------------------------------------------------------------------ #
def test_sharded_apply_delta_answers_and_shard_routing():
    g = erdos_renyi(300, 1.8, 4, seed=47)
    svc = ShardedRLCService.build(
        g, ShardedServiceConfig(k=2, num_shards=4, num_replicas=2,
                                use_device=False, build_backend="numpy",
                                delta_fallback_frac=1.0))
    rng = np.random.default_rng(48)
    for step in range(2):
        delta = random_delta(svc.graph, 1, 1, rng)
        summary = svc.apply_delta(delta)
        assert summary["generation"] == step + 1
        touched = set(summary["shards_touched"])
        assert touched <= {0, 1, 2, 3}
        if not summary["delta"]["fallback"]:
            # untouched shards kept their replicas (old generation)
            for rs in svc.shards:
                if rs.shard_id in touched:
                    assert rs.generation == summary["generation"]
                else:
                    assert rs.generation < summary["generation"]
        queries = sample_queries(svc.graph, 2, rng)
        got = svc.query_batch(queries)
        want = reference_answers(svc.graph, 2, queries)
        assert got == want
    assert svc.stats()["deltas_applied"] == 2


def test_sharded_delta_cache_invalidation_and_hot_swap_clear():
    g = erdos_renyi(200, 2.0, 3, seed=49)
    svc = ShardedRLCService.build(
        g, ShardedServiceConfig(k=2, num_shards=2, use_device=False,
                                build_backend="numpy",
                                delta_fallback_frac=1.0))
    rng = np.random.default_rng(50)
    queries = sample_queries(svc.graph, 2, rng, n_per_mr=8)
    svc.query_batch(queries)
    primed = set(svc.cache._d)
    assert primed
    summary = svc.apply_delta(random_delta(svc.graph, 1, 1, rng))
    dirty_s = set(summary["dirty_out"])
    dirty_t = set(summary["dirty_in"])
    survivors = set(svc.cache._d)
    for (s, t, mr) in primed - survivors:
        assert s in dirty_s or t in dirty_t
    for (s, t, mr) in survivors:
        assert s not in dirty_s and t not in dirty_t
    got = svc.query_batch(queries)
    assert got == reference_answers(svc.graph, 2, queries)
    # hot_swap wipes the whole cache (coarse invalidation)
    svc.query_batch(queries)
    assert len(svc.cache) > 0
    svc.hot_swap()
    assert len(svc.cache) == 0
    got = svc.query_batch(queries)
    assert got == reference_answers(svc.graph, 2, queries)


def test_apply_delta_on_adopted_index_with_nondefault_flags():
    """An index adopted pre-built with non-default pruning flags has a
    different entry-set vintage than the delta builder's rebuild; the
    bootstrap must resync the whole serving state so later row patches
    never mix vintages (stale unpruned entries in clean rows)."""
    from repro.build import get_backend
    g = erdos_renyi(100, 2.2, 3, seed=57)
    idx = get_backend("numpy", use_pr1=False).build(g, 2)[0]
    svc = RLCService.build(
        g, ServiceConfig(k=2, use_device=False, build_backend="numpy",
                         delta_fallback_frac=1.0), index=idx)
    rng = np.random.default_rng(58)
    for _ in range(2):
        # deletion-heavy deltas: exactly the shape that leaves stale
        # reachability entries behind if vintages mix
        svc.apply_delta(random_delta(svc.graph, 1, 2, rng))
        queries = sample_queries(svc.graph, 2, rng)
        assert svc.query_batch(queries) == \
            reference_answers(svc.graph, 2, queries)


def test_sharded_hot_swap_resets_delta_builder():
    """A hot_swap replaces the serving graph; a later apply_delta must
    re-bootstrap from the swapped state, not silently revert to the
    delta builder's cached pre-swap graph."""
    g = erdos_renyi(80, 2.0, 3, seed=54)
    svc = ShardedRLCService.build(
        g, ShardedServiceConfig(k=2, num_shards=2, use_device=False,
                                build_backend="numpy",
                                delta_fallback_frac=1.0))
    rng = np.random.default_rng(55)
    svc.apply_delta(random_delta(svc.graph, 1, 1, rng))   # caches builder
    g2 = erdos_renyi(80, 2.4, 3, seed=56)
    svc.hot_swap(graph=g2)
    assert svc.graph is g2
    delta = random_delta(g2, 1, 1, rng)
    svc.apply_delta(delta)
    want_graph = g2.apply_delta(delta)
    assert set(map(tuple, svc.graph.edges.tolist())) == \
        set(map(tuple, want_graph.edges.tolist()))
    queries = sample_queries(svc.graph, 2, rng)
    assert svc.query_batch(queries) == \
        reference_answers(svc.graph, 2, queries)


# ------------------------------------------------------------------ #
# close() / context manager stops the deadline ticker
# ------------------------------------------------------------------ #
def _assert_close_stops_ticker(svc):
    fired = threading.Event()
    svc.batcher.start_ticker(lambda batch: fired.set())
    assert svc.batcher.ticker_running
    svc.close()
    assert not svc.batcher.ticker_running
    svc.close()                                  # idempotent
    assert not svc.batcher.ticker_running
    # a stopped ticker's thread is joined: no new flushes fire
    fired.clear()
    svc.query(0, 1, (0,))
    time.sleep(0.02)
    assert not svc.batcher.ticker_running


def test_service_close_stops_ticker():
    g = erdos_renyi(30, 2.0, 2, seed=51)
    svc = RLCService.build(g, ServiceConfig(k=2, use_device=False,
                                            max_wait_ms=1.0))
    _assert_close_stops_ticker(svc)


def test_sharded_service_close_stops_ticker():
    g = erdos_renyi(60, 2.0, 2, seed=52)
    svc = ShardedRLCService.build(
        g, ShardedServiceConfig(k=2, num_shards=2, use_device=False,
                                max_wait_ms=1.0))
    _assert_close_stops_ticker(svc)


def test_service_context_manager():
    g = erdos_renyi(30, 2.0, 2, seed=53)
    with RLCService.build(g, ServiceConfig(k=2, use_device=False)) as svc:
        svc.batcher.start_ticker(lambda batch: None)
        assert svc.query(0, 1, (0,)) in (True, False)
    assert not svc.batcher.ticker_running
    # closed services still answer synchronous queries
    assert svc.query(0, 1, (0,)) in (True, False)
