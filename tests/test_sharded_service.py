"""Sharded multi-host serving (repro.service.sharded.*): shard planner,
frozen-slice views, two-sided router, scatter/gather fan-out, replica
hot-swap, and sharded-vs-single-host agreement (ISSUE-3 acceptance:
bit-identical answers over shard counts {1, 2, 4} x replicas {1, 2} on
>= 3 random graphs, plus a passing mid-stream hot-swap test)."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.baselines import bibfs_rlc
from repro.core.index_builder import build_rlc_index
from repro.core.minimum_repeat import enumerate_mrs, mr_id_space
from repro.core.rlc_index import merge_join_rows
from repro.graphgen import barabasi_albert, erdos_renyi
from repro.service import RLCService, ServiceConfig
from repro.service.sharded import (ShardedRLCService, ShardedServiceConfig,
                                   TwoSidedRouter, plan_shards)


def _frozen(g, k=2):
    idx = build_rlc_index(g, k)
    ids = mr_id_space(g.num_labels, k)
    return idx, ids, idx.freeze(ids)


# ------------------------------------------------------------------ #
# Shard planner
# ------------------------------------------------------------------ #
def test_plan_contiguous_and_covering():
    g = erdos_renyi(80, 3.0, 3, seed=1)
    _, _, frozen = _frozen(g)
    for S in (1, 2, 3, 4, 8):
        plan = plan_shards(frozen, S)
        assert plan.num_shards == S
        assert plan.starts[0] == 0 and plan.starts[-1] == 80
        assert np.all(np.diff(plan.starts) >= 1)    # every shard non-empty
        # every vertex maps into the shard whose range contains it
        for v in range(80):
            s = plan.shard_of(v)
            lo, hi = plan.range(s)
            assert lo <= v < hi
        np.testing.assert_array_equal(
            plan.shard_of_batch(np.arange(80)),
            [plan.shard_of(v) for v in range(80)])


def test_plan_balances_by_entries_not_vertices():
    # hub-heavy head: BA graphs concentrate entries on early vertices
    g = barabasi_albert(120, 3, 3, seed=5)
    _, _, frozen = _frozen(g)
    plan = plan_shards(frozen, 4)
    w = frozen.entry_weights()
    per_shard = [int(w[lo:hi].sum()) for lo, hi in plan.ranges()]
    vertices = [hi - lo for lo, hi in plan.ranges()]
    # entry counts stay near-balanced ...
    assert max(per_shard) <= 2.0 * (sum(per_shard) / len(per_shard))
    # ... which for a skewed graph forces unequal vertex counts
    assert max(vertices) > min(vertices)


def test_plan_rejects_bad_shard_counts():
    g = erdos_renyi(10, 2.0, 2, seed=0)
    _, _, frozen = _frozen(g)
    with pytest.raises(ValueError):
        plan_shards(frozen, 0)
    with pytest.raises(ValueError):
        plan_shards(frozen, 11)


# ------------------------------------------------------------------ #
# Frozen slice views
# ------------------------------------------------------------------ #
def test_slice_rows_zero_copy_and_query_equivalence():
    g = erdos_renyi(50, 3.0, 3, seed=3)
    idx, ids, frozen = _frozen(g)
    sl = frozen.slice_rows(10, 35)
    # entry arrays are views of the parent's buffers, not copies
    assert sl.out_hub.base is not None and sl.in_hub.base is not None
    assert sl.num_entries() <= frozen.num_entries()
    mrs = enumerate_mrs(3, 2)
    rng = np.random.default_rng(4)
    for _ in range(150):
        s, t = int(rng.integers(10, 35)), int(rng.integers(10, 35))
        m = int(rng.integers(len(mrs)))
        # both endpoints in range: the slice answers exactly like the parent
        assert sl.query(s, t, m) == frozen.query(s, t, m)
    # out-of-range s sees an empty out-row (the routing contract)
    oh, _ = sl.row_out(5)
    assert len(oh) == 0


def test_slice_digest_join_matches_full_index():
    """Cross-shard contract: s's out-row digest + t-owner's local in-row
    through merge_join_rows == the unsharded answer."""
    g = erdos_renyi(50, 3.5, 3, seed=8)
    _, ids, frozen = _frozen(g)
    left, right = frozen.slice_rows(0, 25), frozen.slice_rows(25, 50)
    mrs = enumerate_mrs(3, 2)
    rng = np.random.default_rng(9)
    for _ in range(150):
        s, t = int(rng.integers(0, 25)), int(rng.integers(25, 50))
        m = int(rng.integers(len(mrs)))
        oh, om = left.row_out(s)        # the shipped digest
        ih, im = right.row_in(t)        # in-side owner's local row
        got = merge_join_rows(oh, om, ih, im, frozen.aid, s, t, m)
        assert got == frozen.query(s, t, m), (s, t, m)


def test_slice_rows_rejects_bad_range():
    g = erdos_renyi(20, 2.0, 2, seed=0)
    _, _, frozen = _frozen(g)
    with pytest.raises(ValueError):
        frozen.slice_rows(-1, 10)
    with pytest.raises(ValueError):
        frozen.slice_rows(5, 21)


# ------------------------------------------------------------------ #
# Two-sided router
# ------------------------------------------------------------------ #
def test_router_invariant_home_is_shard_t():
    g = erdos_renyi(40, 3.0, 3, seed=2)
    _, _, frozen = _frozen(g)
    router = TwoSidedRouter(plan_shards(frozen, 4))
    rng = np.random.default_rng(6)
    for _ in range(100):
        s, t = int(rng.integers(40)), int(rng.integers(40))
        r = router.route(s, t)
        assert r.home == r.shard_t == router.plan.shard_of(t)
        assert r.local == (router.plan.shard_of(s) == r.shard_t)
    st_ = router.stats()
    assert st_["local"] + st_["remote"] == 100
    assert sum(st_["pairs"].values()) == 100


# ------------------------------------------------------------------ #
# Sharded vs single-host agreement (property)
# ------------------------------------------------------------------ #
@settings(max_examples=4, deadline=None)
@given(st.integers(0, 10_000), st.integers(40, 70))
def test_sharded_matches_single_host_and_oracle(seed, n):
    """>= 3 random graphs (4 examples) x shards {1,2,4} x replicas
    {1,2}: bit-identical to RLCService and the BiBFS oracle."""
    g = erdos_renyi(n, 3.5, 3, seed=seed)
    base = RLCService.build(
        g, ServiceConfig(k=2, batch_size=8, cache_capacity=128))
    rng = np.random.default_rng(seed + 1)
    mrs = enumerate_mrs(3, 2)
    queries = [(int(rng.integers(n)), int(rng.integers(n)),
                mrs[int(rng.integers(len(mrs)))]) for _ in range(100)]
    want = base.query_batch(queries)
    oracle = [bibfs_rlc(g, s, t, L) for s, t, L in queries]
    assert want == oracle
    for num_shards in (1, 2, 4):
        for num_replicas in (1, 2):
            svc = ShardedRLCService.build(
                g, ShardedServiceConfig(
                    k=2, batch_size=8, cache_capacity=128,
                    num_shards=num_shards, num_replicas=num_replicas),
                index=base.index)
            got = svc.query_batch(queries)
            assert got == want, (num_shards, num_replicas)
            # replay through the warm cache: still identical
            assert svc.query_batch(queries) == want


def test_sharded_exercises_cross_shard_paths():
    g = erdos_renyi(60, 4.0, 3, seed=21)
    svc = ShardedRLCService.build(
        g, ShardedServiceConfig(k=2, batch_size=8, cache_capacity=0,
                                num_shards=4, num_replicas=2))
    rng = np.random.default_rng(22)
    mrs = enumerate_mrs(3, 2)
    queries = [(int(rng.integers(60)), int(rng.integers(60)),
                mrs[int(rng.integers(len(mrs)))]) for _ in range(160)]
    got = svc.query_batch(queries)
    assert got == [bibfs_rlc(g, s, t, L) for s, t, L in queries]
    st_ = svc.stats()
    assert st_["router"]["remote"] > 0 and st_["router"]["local"] > 0
    ex = st_["executor"]
    assert ex["remote"]["queries"] >= st_["router"]["remote"] or \
        ex["remote"]["batches"] > 0
    assert ex["remote_joins_device"] + ex["remote_joins_numpy"] > 0
    assert ex["digest_bytes"] > 0


def test_sharded_accepts_string_constraints_and_rejects_bad_input():
    g = erdos_renyi(30, 3.0, 2, seed=12)
    svc = ShardedRLCService.build(
        g, ShardedServiceConfig(k=2, num_shards=2))
    base = RLCService.build(g, ServiceConfig(k=2), index=svc.index)
    assert svc.query(0, 17, "(0 1)+") == base.query(0, 17, "(0 1)+")
    with pytest.raises(ValueError):
        svc.query(0, 99, "(0)+")


# ------------------------------------------------------------------ #
# Replica hot-swap
# ------------------------------------------------------------------ #
def test_hot_swap_mid_stream():
    """Serve -> swap in an index for a denser graph -> keep serving: the
    stream's answers flip to the new graph's truth, the cache never leaks
    stale answers, every shard reports the new generation."""
    n = 50
    g1 = erdos_renyi(n, 2.0, 3, seed=31)
    g2 = erdos_renyi(n, 5.0, 3, seed=32)
    svc = ShardedRLCService.build(
        g1, ShardedServiceConfig(k=2, batch_size=8, cache_capacity=256,
                                 num_shards=4, num_replicas=2))
    rng = np.random.default_rng(33)
    mrs = enumerate_mrs(3, 2)
    queries = [(int(rng.integers(n)), int(rng.integers(n)),
                mrs[int(rng.integers(len(mrs)))]) for _ in range(80)]
    want1 = [bibfs_rlc(g1, s, t, L) for s, t, L in queries]
    want2 = [bibfs_rlc(g2, s, t, L) for s, t, L in queries]
    assert want1 != want2   # the swap must be observable
    assert svc.query_batch(queries) == want1
    gen = svc.hot_swap(graph=g2)
    assert gen == 1
    assert svc.query_batch(queries) == want2    # cache was invalidated
    st_ = svc.stats()
    assert st_["index"]["generation"] == 1
    for sh in st_["shards"]:
        assert sh["generation"] == 1 and sh["swaps"] == 1


def test_hot_swap_noop_refresh_keeps_answers():
    g = erdos_renyi(40, 3.0, 3, seed=41)
    svc = ShardedRLCService.build(
        g, ShardedServiceConfig(k=2, num_shards=2, num_replicas=2))
    rng = np.random.default_rng(42)
    mrs = enumerate_mrs(3, 2)
    queries = [(int(rng.integers(40)), int(rng.integers(40)),
                mrs[int(rng.integers(len(mrs)))]) for _ in range(60)]
    before = svc.query_batch(queries)
    assert svc.hot_swap() == 1          # re-freeze of the same index
    assert svc.query_batch(queries) == before


def test_hot_swap_rejects_mismatched_graph():
    g = erdos_renyi(40, 3.0, 3, seed=51)
    svc = ShardedRLCService.build(
        g, ShardedServiceConfig(k=2, num_shards=2))
    with pytest.raises(ValueError):
        svc.hot_swap(graph=erdos_renyi(41, 3.0, 3, seed=52))
    with pytest.raises(ValueError):
        svc.hot_swap(index=build_rlc_index(g, 1))   # k mismatch


def test_replicas_share_windowed_device_layout():
    """Per-shard device arrays cover only the shard's row window (memory
    really shrinks ~1/S) and a shard's replicas share one immutable
    layout object instead of re-packing it per replica."""
    g = erdos_renyi(60, 3.0, 3, seed=91)
    svc = ShardedRLCService.build(
        g, ShardedServiceConfig(k=2, num_shards=4, num_replicas=2))
    for rs in svc.shards:
        r0, r1 = rs.replicas
        assert r0.device_index is r1.device_index
        assert r0.device_index.out_hub.shape[0] == rs.hi - rs.lo
        assert r0.device_index.row_lo == rs.lo
    gen_layouts = [rs.replicas[0].device_index for rs in svc.shards]
    svc.hot_swap()
    for rs, old in zip(svc.shards, gen_layouts):
        r0, r1 = rs.replicas
        assert r0.device_index is r1.device_index   # still shared ...
        assert r0.device_index is not old           # ... but rebuilt


@pytest.mark.slow
def test_sharded_agreement_heavy_sweep():
    """Paper-scale-ish sweep (deselected by default; run `pytest -m slow`):
    8-way sharding on a 400-vertex hub-skewed graph, swap under a longer
    stream."""
    n = 400
    g = barabasi_albert(n, 3, 4, seed=71)
    base = RLCService.build(
        g, ServiceConfig(k=2, batch_size=32, cache_capacity=1024))
    rng = np.random.default_rng(72)
    mrs = enumerate_mrs(4, 2)
    queries = [(int(rng.integers(n)), int(rng.integers(n)),
                mrs[int(rng.integers(len(mrs)))]) for _ in range(600)]
    want = base.query_batch(queries)
    for num_shards in (2, 8):
        svc = ShardedRLCService.build(
            g, ShardedServiceConfig(k=2, batch_size=32, cache_capacity=1024,
                                    num_shards=num_shards, num_replicas=2),
            index=base.index)
        assert svc.query_batch(queries) == want
        g2 = erdos_renyi(n, 4.0, 4, seed=73)
        svc.hot_swap(graph=g2)
        assert svc.query_batch(queries[:200]) == \
            [bibfs_rlc(g2, s, t, L) for s, t, L in queries[:200]]


# ------------------------------------------------------------------ #
# Stats surface
# ------------------------------------------------------------------ #
def test_sharded_stats_per_shard_breakdown():
    g = erdos_renyi(60, 3.0, 3, seed=61)
    svc = ShardedRLCService.build(
        g, ShardedServiceConfig(k=2, batch_size=8, num_shards=4,
                                num_replicas=2))
    rng = np.random.default_rng(62)
    mrs = enumerate_mrs(3, 2)
    svc.query_batch([(int(rng.integers(60)), int(rng.integers(60)),
                      mrs[int(rng.integers(len(mrs)))]) for _ in range(40)])
    st_ = svc.stats()
    assert 0.0 <= st_["cache"]["hit_rate"] <= 1.0
    shards = st_["shards"]
    assert len(shards) == 4
    assert sum(sh["entries"] for sh in shards) == st_["index"]["entries"]
    for sh in shards:
        assert sh["size_bytes"] > 0 and sh["replicas"] == 2
        assert sh["hi"] > sh["lo"]
    # nested executor shape: latencies and traffic live together
    assert set(st_["executor"]) >= {"local", "remote", "sub_batches",
                                    "digest_bytes"}


# ------------------------------------------------------------------ #
# Device faults surface (no silent host fallback)
# ------------------------------------------------------------------ #
def test_sharded_device_layout_failure_raises(monkeypatch):
    from repro.core.device_index import DeviceIndex

    def boom(*a, **kw):
        raise RuntimeError("no device memory")

    g = erdos_renyi(40, 3.0, 3, seed=3)
    monkeypatch.setattr(DeviceIndex, "from_frozen", boom)
    with pytest.raises(RuntimeError, match="no device memory"):
        ShardedRLCService.build(g, ShardedServiceConfig(k=2, num_shards=2))


def test_pin_raises_when_placement_fails():
    from repro.service.sharded.replica import _pin

    g = erdos_renyi(20, 3.0, 2, seed=4)
    _, ids, frozen = _frozen(g)
    from repro.core.device_index import DeviceIndex
    layout = DeviceIndex.from_frozen(frozen, ids)
    assert _pin(layout, None) is layout
    with pytest.raises(Exception):
        _pin(layout, object())          # not a device


def test_cross_shard_device_join_failure_raises(monkeypatch):
    """An in-process cross-shard device join that fails raises; it does
    not fall back to the host numpy join or to BiBFS."""
    from repro.service.sharded import fanout

    g = erdos_renyi(40, 3.5, 3, seed=5)
    svc = ShardedRLCService.build(
        g, ShardedServiceConfig(k=2, batch_size=8, cache_capacity=0,
                                num_shards=2))

    def boom(*a, **kw):
        raise RuntimeError("device lost")

    monkeypatch.setattr(fanout.ScatterGatherExecutor, "_join_device", boom)
    lo, hi = svc.plan.range(1)
    with pytest.raises(RuntimeError, match="device lost"):
        svc.query_batch([(0, lo, (0,))])
    assert svc.fanout.remote_joins_numpy == 0 and svc.fanout.degraded == 0
