"""Serving control plane: SLO-aware batching, admission control +
shedding, prioritized cache warming (:mod:`repro.service.control`).

The overload tests drive the service open-loop through a
:class:`VirtualClock`: the test advances the clock to each arrival's
stamp while the service advances it by measured execute time, so queue
waits accumulate exactly as they would in an open-loop server at an
offered load above capacity — deterministic overload without threads.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core.baselines import bibfs_rlc
from repro.core.queries import biased_true_queries
from repro.graphgen import erdos_renyi
from repro.graphgen.generators import random_delta
from repro.obs import MetricsRegistry
from repro.service import (SHED, AdmissionController, CacheWarmer,
                           FrequencySketch, MicroBatcher, ResultCache,
                           RLCService, ServiceConfig, ShardedRLCService,
                           ShardedServiceConfig, SLOBatchController,
                           VirtualClock)


def _graph(n=100, seed=7):
    return erdos_renyi(n, 3.5, 3, seed=seed)


def _pool(g, k=2, n=24, seed=3):
    qs = biased_true_queries(g, k, n=n, seed=seed)
    return qs.true_queries + qs.false_queries


# --------------------------------------------------------------------- #
# SHED sentinel
# --------------------------------------------------------------------- #
def test_shed_is_not_a_boolean():
    assert repr(SHED) == "SHED"
    with pytest.raises(TypeError):
        bool(SHED)
    assert SHED is SHED


# --------------------------------------------------------------------- #
# VirtualClock
# --------------------------------------------------------------------- #
def test_virtual_clock_monotone():
    c = VirtualClock()
    assert c() == 0.0
    c.advance(1.5)
    c.advance(-3.0)         # negative advances are ignored
    assert c() == 1.5
    c.at_least(1.0)         # never goes backwards
    assert c() == 1.5
    c.at_least(4.0)
    assert c() == 4.0


# --------------------------------------------------------------------- #
# FrequencySketch
# --------------------------------------------------------------------- #
def test_sketch_estimates_and_hot_set():
    sk = FrequencySketch(width=512, depth=4, hot_capacity=4,
                         decay_every=10 ** 9)
    for _ in range(50):
        sk.observe((1, 2, 0), mr_len=1)
    for _ in range(10):
        sk.observe((3, 4, 0), mr_len=2)
    sk.observe((5, 6, 1), mr_len=3)
    assert sk.estimate((1, 2, 0)) >= 50      # count-min overestimates only
    assert sk.estimate((3, 4, 0)) >= 10
    assert sk.estimate((9, 9, 9)) < 50       # cold key stays (near) zero
    hot = sk.hot(2)
    assert hot[0][2] == (1, 2, 0)
    assert hot[1][2] == (3, 4, 0)


def test_sketch_decay_halves_counts():
    sk = FrequencySketch(width=256, depth=2, decay_every=10 ** 9)
    for _ in range(40):
        sk.observe((7, 8, 0))
    before = sk.estimate((7, 8, 0))
    sk.decay()
    assert sk.estimate((7, 8, 0)) == before // 2
    assert sk.decays == 1


def test_sketch_hot_capacity_bounded():
    sk = FrequencySketch(hot_capacity=8, decay_every=10 ** 9)
    for i in range(100):
        for _ in range(i % 5 + 1):
            sk.observe((i, i, 0), mr_len=1)
    assert len(sk.hot()) <= 8


# --------------------------------------------------------------------- #
# SLO controller
# --------------------------------------------------------------------- #
def test_slo_controller_converges_on_bimodal_workload():
    """Synthetic bimodal workload: MR length 1 is cheap (0.1ms/batch),
    MR length 3 is expensive (8ms/batch, past the shrink threshold of a
    10ms SLO). The controller must grow the cheap bucket's batches (its
    fill says demand exists) and shrink the expensive bucket's, and give
    the expensive bucket a tighter deadline."""
    clock = VirtualClock()
    ctl = SLOBatchController(MetricsRegistry(), target_p99_s=0.010,
                            base_batch=8, base_wait_s=0.002,
                            max_batch=64, interval_s=0.0, clock=clock)
    for _ in range(60):
        clock.advance(0.001)
        # saturating demand: the cheap bucket always flushes full at its
        # current size, the expensive one stays expensive per batch
        ctl.observe_batch(1, n_real=ctl.params(1)[0], exec_s=0.0001)
        ctl.observe_batch(3, n_real=ctl.params(3)[0], exec_s=0.008)
    cheap_b, cheap_w = ctl.params(1)
    exp_b, exp_w = ctl.params(3)
    assert cheap_b == 64, "cheap bucket should grow to max_batch"
    assert exp_b == 1, "expensive bucket should shrink to min_batch"
    assert exp_w < cheap_w, "expensive bucket gets the tighter deadline"
    assert cheap_w <= 0.005      # never above target/2
    st = ctl.stats()
    assert st["updates"] > 0
    assert st["batch_size"][1] == 64 and st["batch_size"][3] == 1


def test_slo_controller_steers_the_scheduler():
    """The batcher consults the controller per bucket: a grown batch
    size changes the full-flush threshold."""
    clock = VirtualClock()
    ctl = SLOBatchController(MetricsRegistry(), target_p99_s=0.010,
                            base_batch=2, base_wait_s=1.0,
                            max_batch=8, interval_s=0.0, clock=clock)
    b = MicroBatcher(2, 1.0, clock=clock, params_fn=ctl.params)
    # before any feedback: flushes at the base size of 2
    _, ready = b.submit(0, 1, 0, 1)
    _, ready = b.submit(2, 3, 0, 1)
    assert len(ready) == 1 and ready[0].n_real == 2
    # cheap + full feedback grows the bucket to 4
    for _ in range(10):
        clock.advance(0.001)
        ctl.observe_batch(1, n_real=2, exec_s=0.0001)
    grown, _w = ctl.params(1)
    assert grown > 2
    for i in range(grown - 1):
        _, ready = b.submit(10 + i, 1, 0, 1)
        assert ready == []
    _, ready = b.submit(50, 1, 0, 1)
    assert len(ready) == 1 and ready[0].n_real == grown


def test_slo_controller_rejects_bad_target():
    with pytest.raises(ValueError):
        SLOBatchController(MetricsRegistry(), target_p99_s=0.0,
                           base_batch=8, base_wait_s=0.002)


# --------------------------------------------------------------------- #
# scheduler: no padding, eviction, priority scans
# --------------------------------------------------------------------- #
def test_flush_carries_real_slots_only():
    clock = [0.0]
    b = MicroBatcher(8, 0.5, clock=lambda: clock[0])
    b.submit(0, 1, 0, 1)
    b.submit(2, 3, 0, 1)
    clock[0] = 1.0
    ready = b.poll()
    assert len(ready) == 1
    assert len(ready[0].s) == 2 == ready[0].n_real
    assert ready[0].n_padding == 0


def test_evict_removes_queued_request():
    b = MicroBatcher(8, 100.0, clock=lambda: 0.0)
    r1, _ = b.submit(0, 1, 0, 1)
    r2, _ = b.submit(2, 3, 0, 1)
    assert b.evict(r1) is True
    assert b.pending() == 1
    assert not b.is_inflight((0, 1, 0))
    assert b.evict(r1) is False          # already gone
    ready = b.drain()
    assert [r.req_id for r in ready[0].requests] == [r2.req_id]


def test_priority_scans():
    b = MicroBatcher(8, 100.0, clock=lambda: 0.0)
    assert b.lowest_priority_pending(lambda r: r.s) is None
    assert b.median_pending_priority(lambda r: r.s) is None
    for s in (5, 1, 9):
        b.submit(s, 0, 0, 1)
    worst = b.lowest_priority_pending(lambda r: r.s)
    assert worst.s == 1
    assert b.median_pending_priority(lambda r: r.s) == 5


# --------------------------------------------------------------------- #
# admission controller (unit)
# --------------------------------------------------------------------- #
def _sketch_with(keys):
    sk = FrequencySketch(decay_every=10 ** 9)
    for key, count, mr_len in keys:
        for _ in range(count):
            sk.observe(key, mr_len)
    return sk


def test_admission_hard_bound_sheds_coldest_deepest():
    hot, cold = (1, 1, 0), (2, 2, 1)
    sk = _sketch_with([(hot, 50, 1), (cold, 1, 3)])
    adm = AdmissionController(MetricsRegistry(), sk, max_pending=1)
    b = MicroBatcher(64, 100.0, clock=lambda: 0.0)
    assert adm.decide(cold, 3, b)[0] == "admit"
    b.submit(*cold, 3)
    # queue full; the hot short arrival evicts the cold deep victim
    decision, victim = adm.decide(hot, 1, b)
    assert decision == "evict" and victim.key == cold
    b.evict(victim)
    b.submit(*hot, 1)
    # queue full again; a second cold arrival is shed outright
    decision, victim = adm.decide(cold, 3, b)
    assert decision == "shed" and victim is None
    # two requests were shed in total: the evicted victim + this arrival
    assert adm.stats()["shed"] == 2


def test_admission_backpressure_sheds_low_priority_and_recovers():
    hot, cold = (1, 1, 0), (2, 2, 1)
    sk = _sketch_with([(hot, 50, 1), (cold, 1, 3)])
    adm = AdmissionController(MetricsRegistry(), sk,
                              backpressure_s=0.010)
    b = MicroBatcher(64, 100.0, clock=lambda: 0.0)
    b.submit(*hot, 1)
    b.submit(*cold, 3)
    assert not adm.backpressured
    for _ in range(20):
        adm.observe_wait(0.050)          # queue waits blow past 10ms
    assert adm.backpressured
    assert adm.decide(cold, 3, b)[0] == "shed"
    assert adm.decide(hot, 1, b)[0] == "admit"   # hot short still flows
    for _ in range(50):
        adm.observe_wait(0.0001)         # backlog drained
    assert not adm.backpressured
    assert adm.decide(cold, 3, b)[0] == "admit"  # shedding recovered


# --------------------------------------------------------------------- #
# service-level overload: shed under 2x capacity, recover after
# --------------------------------------------------------------------- #
def _overloaded_service(g, clock, **cfg):
    return RLCService.build(g, ServiceConfig(
        k=2, batch_size=8, max_wait_ms=2.0, backend="numpy",
        use_device=False, cache_capacity=0, clock=clock, **cfg))


def test_service_sheds_under_injected_overload_and_recovers():
    g = _graph()
    pool = _pool(g)
    clock = VirtualClock()
    svc = _overloaded_service(g, clock, admission_max_pending=4,
                              admission_backpressure_ms=1.0)
    # capacity run: arrivals spaced far apart -> zero shed
    for s, t, c in pool[:12]:
        clock.advance(1.0)
        assert svc.query_batch([(s, t, c)])[0] is not SHED
    assert svc.queries_shed == 0
    # overload: all arrivals at one instant, far past max_pending — the
    # hard bound must shed the overflow with the explicit sentinel
    ans = svc.query_batch(pool)
    shed = [a for a in ans if a is SHED]
    assert shed, "hard admission bound never shed under 6x pending"
    assert svc.queries_shed == len(shed)
    assert svc.stats()["control"]["admission"]["shed"] >= len(shed)
    # non-shed answers stay bit-identical to the oracle
    for (s, t, c), a in zip(pool, ans):
        if a is not SHED:
            assert bool(a) == bibfs_rlc(g, s, t, svc.parse(c).mr)
    # recovery: spaced arrivals again -> no further shedding
    before = svc.queries_shed
    for s, t, c in pool[:12]:
        clock.advance(1.0)
        svc.query_batch([(s, t, c)])
    assert svc.queries_shed == before


def test_no_shedding_at_offered_load_below_capacity():
    g = _graph()
    pool = _pool(g)
    clock = VirtualClock()
    svc = _overloaded_service(g, clock, target_p99_ms=50.0,
                              admission_max_pending=256)
    for chunk in range(0, len(pool), 8):
        clock.advance(1.0)               # arrivals well under capacity
        ans = svc.query_batch(pool[chunk:chunk + 8])
        assert all(a is not SHED for a in ans)
    assert svc.queries_shed == 0


# --------------------------------------------------------------------- #
# cache warmer
# --------------------------------------------------------------------- #
def _warmer(cache, sk, budget_bytes=1 << 20, budget_s=10.0, chunk=4,
            fail_epoch=None):
    calls = []

    def execute(s, t, mr, mr_len):
        calls.append(len(s))
        return np.ones(len(s), dtype=bool)

    w = CacheWarmer(cache, sk, execute, budget_bytes=budget_bytes,
                    budget_s=budget_s, chunk=chunk)
    return w, calls


def test_warmer_fills_hot_uncached_keys():
    cache = ResultCache(64)
    sk = _sketch_with([((1, 2, 0), 30, 1), ((3, 4, 0), 20, 1),
                       ((5, 6, 1), 10, 2)])
    cache.put((1, 2, 0), True, mr_len=1)     # hottest already cached
    w, calls = _warmer(cache, sk)
    rep = w.warm("manual")
    assert rep["warmed"] == 2
    assert cache.peek((3, 4, 0)) is True
    assert cache.peek((5, 6, 1)) is True
    assert rep["stale"] == 0


def test_warmer_respects_byte_budget():
    cache = ResultCache(1024)
    sk = FrequencySketch(hot_capacity=64, decay_every=10 ** 9)
    for i in range(32):
        for _ in range(2):
            sk.observe((i, i + 1, 0), 1)
    budget_keys = 5
    w, calls = _warmer(cache, sk,
                       budget_bytes=budget_keys * CacheWarmer.ENTRY_BYTES)
    rep = w.warm("manual")
    assert rep["warmed"] <= budget_keys
    assert rep["bytes"] <= budget_keys * CacheWarmer.ENTRY_BYTES
    assert rep["skipped_budget"] >= 32 - budget_keys
    assert len(cache) == rep["warmed"]


def test_warmer_epoch_fenced_mid_pass():
    """A mutation landing while a warm chunk executes must abort the
    pass: answers computed against the dead index never enter the
    cache (mirrors the shadow verifier's discard-on-mutation fencing)."""
    cache = ResultCache(1024)
    sk = FrequencySketch(hot_capacity=64, decay_every=10 ** 9)
    for i in range(12):
        sk.observe((i, i + 1, 0), 1)
    w = None

    def execute(s, t, mr, mr_len):
        w.bump_epoch()                    # delta lands mid-execute
        return np.ones(len(s), dtype=bool)

    w = CacheWarmer(cache, sk, execute, budget_bytes=1 << 20,
                    budget_s=10.0, chunk=4)
    rep = w.warm("apply_delta")
    assert rep["warmed"] == 0
    assert rep["stale"] > 0
    assert len(cache) == 0


def test_service_warm_after_apply_delta_is_epoch_consistent():
    """End-to-end: warming runs after apply_delta against the *new*
    index; every warmed answer matches the post-delta oracle."""
    g = _graph(80, seed=11)
    svc = RLCService.build(g, ServiceConfig(
        k=2, batch_size=8, backend="numpy", use_device=False,
        cache_capacity=256, warm_capacity=64))
    pool = _pool(g, n=16, seed=5)
    for _ in range(3):
        svc.query_batch(pool)            # populate the sketch
    delta = random_delta(svc.graph, 2, 2, np.random.default_rng(0))
    rep = svc.apply_delta(delta)
    assert rep["warm"] is not None and rep["warm"]["trigger"] == "apply_delta"
    assert rep["warm"]["stale"] == 0
    g2 = svc.graph
    for key in list(svc.cache._d):
        s, t, mr_id = key
        val = svc.cache.peek(key)
        assert val == bibfs_rlc(g2, s, t, svc._id_to_mr[mr_id])


def test_sharded_warm_after_hot_swap_raises_early_hit_rate():
    """The acceptance-shaped check: after hot_swap (cache cleared), the
    warmed service hits on early queries where the unwarmed one cold
    misses."""
    g = _graph(100, seed=13)
    pool = _pool(g, n=20, seed=9)
    rng = np.random.default_rng(2)
    zipf = rng.choice(len(pool), size=300,
                      p=(lambda w: w / w.sum())(
                          1.0 / np.arange(1, len(pool) + 1)))
    stream = [pool[i] for i in zipf]
    rates = {}
    for warm_capacity in (0, 128):
        svc = ShardedRLCService.build(g, ShardedServiceConfig(
            k=2, num_shards=2, num_replicas=1, use_device=False,
            batch_size=8, cache_capacity=1024,
            warm_capacity=warm_capacity))
        svc.query_batch(stream)          # populate sketch + cache
        svc.hot_swap()                   # clears the cache; warms if on
        pre = svc.cache.stats.hits
        svc.query_batch(stream[:100])
        rates[warm_capacity] = svc.cache.stats.hits - pre
    assert rates[128] > rates[0], (
        f"warmed first-100 hits {rates[128]} <= unwarmed {rates[0]}")


# --------------------------------------------------------------------- #
# mid-swap BiBFS degradation
# --------------------------------------------------------------------- #
def test_fanout_degrades_to_bibfs_mid_swap():
    g = _graph(90, seed=17)
    pool = _pool(g, n=12, seed=4)
    svc = ShardedRLCService.build(g, ShardedServiceConfig(
        k=2, num_shards=2, num_replicas=1, use_device=False,
        batch_size=8, cache_capacity=0))
    expected = [bool(a) for a in svc.query_batch(pool)]
    # pin one replica set mid-swap: every sub-batch touching it must
    # take the online-BiBFS path and still answer exactly
    svc.shards[0].swapping = True
    try:
        degraded = svc.query_batch(pool)
    finally:
        svc.shards[0].swapping = False
    assert [bool(a) for a in degraded] == expected
    assert svc.fanout.degraded > 0
    reg = svc.obs.registry
    m = reg.get("rlc_fanout_degraded")
    (_key, cell), = m.series()
    assert cell.value == svc.fanout.degraded
    # swap done: back to the indexed path, no further degradation
    n = svc.fanout.degraded
    svc.query_batch(pool)
    assert svc.fanout.degraded == n


# --------------------------------------------------------------------- #
# cache breakdowns
# --------------------------------------------------------------------- #
def test_cache_hit_rate_excludes_expired_and_breaks_down_by_mr_len():
    clock = [0.0]
    c = ResultCache(8, ttl_s=1.0, clock=lambda: clock[0])
    c.put((1, 1, 0), True, mr_len=1)
    assert c.get((1, 1, 0), mr_len=1) is True        # hit
    assert c.get((2, 2, 0), mr_len=2) is None        # miss
    clock[0] = 2.0
    assert c.get((1, 1, 0), mr_len=1) is None        # expired, not a miss
    assert c.stats.hits == 1
    assert c.stats.misses == 1
    assert c.stats.expirations == 1
    assert c.stats.lookups == 3
    assert c.stats.hit_rate == pytest.approx(1 / 3)
    by_len = c.hit_rate_by_mr_len()
    assert by_len[1] == pytest.approx(0.5)           # 1 hit, 1 expired
    assert by_len[2] == 0.0
    assert c.stats.as_dict()["hit_rate_by_mr_len"] == by_len


def test_cache_eviction_age_tracked():
    clock = [0.0]
    c = ResultCache(2, clock=lambda: clock[0])
    c.put((1, 1, 0), True)
    clock[0] = 5.0
    c.put((2, 2, 0), True)
    c.put((3, 3, 0), True)              # evicts key 1, aged 5s
    assert c.stats.evictions == 1
    summ = c.eviction_age_summary()
    assert summ["count"] == 1
    assert summ["max"] == pytest.approx(5.0)


def test_cache_mr_lookup_series():
    reg = MetricsRegistry()

    class Obs:
        registry = reg
    c = ResultCache(8, obs=Obs())
    c.put((1, 1, 0), True, mr_len=2)
    c.get((1, 1, 0), mr_len=2)
    c.get((9, 9, 0), mr_len=3)
    m = reg.get("rlc_cache_mr_lookups")
    assert m.value(outcome="hit", mr_len=2) == 1
    assert m.value(outcome="miss", mr_len=3) == 1
