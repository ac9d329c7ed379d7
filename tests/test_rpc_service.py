"""Multi-process RPC shard serving (ISSUE-10 tentpole).

Three layers, cheapest first: wire-codec unit tests, :class:`ShardWorker`
protocol tests driven without any process, then real spawn-based cluster
tests (worker count env-gated via ``RLC_RPC_WORKERS``, default 2) —
bit-identical answers vs the single-process service across mid-stream
hot-swap/apply_delta, worker death, and leave/rejoin. The heavy
shards x replicas sweep is ``slow``-marked.
"""
import os

import numpy as np
import pytest

from repro.build import build_rlc_index
from repro.core.minimum_repeat import enumerate_mrs, mr_id_space
from repro.graphgen import erdos_renyi, random_delta
from repro.service import RLCService, ServiceConfig
from repro.service.rpc import ShardWorker, wire
from repro.service.rpc.controller import StragglerMonitor, _slice_payload
from repro.service.sharded import ShardedRLCService, ShardedServiceConfig
from repro.service.stats import validate_stats

K = 2
#: CI knob: how many worker processes the cheap cluster tests may spawn
WORKERS = max(1, int(os.environ.get("RLC_RPC_WORKERS", "2")))


def _graph(n=80, seed=11):
    return erdos_renyi(n, 3.0, 3, seed=seed)


def _queries(g, n=40, seed=0):
    rng = np.random.default_rng(seed)
    st = rng.integers(0, g.num_vertices, size=(n, 2))
    mrs = list(enumerate_mrs(g.num_labels, K))
    return [(int(s), int(t), mrs[i % len(mrs)])
            for i, (s, t) in enumerate(st)]


def _single(g, **kw):
    cfg = dict(k=K, batch_size=8, backend="numpy", use_device=False)
    cfg.update(kw)
    return RLCService.build(g, ServiceConfig(**cfg))


def _rpc(g, num_shards=None, num_replicas=1, **kw):
    cfg = dict(k=K, batch_size=8, backend="numpy", use_device=False,
               num_shards=num_shards or min(WORKERS, 2),
               num_replicas=num_replicas, transport="rpc")
    cfg.update(kw)
    return ShardedRLCService.build(g, ShardedServiceConfig(**cfg))


def _bools(answers):
    return [bool(a) for a in answers]


# ------------------------------------------------------------------ #
# Wire codec
# ------------------------------------------------------------------ #
def test_wire_roundtrips_scalars_arrays_and_nesting():
    doc = dict(
        method="execute", id=7, ok=True, name="s0r1",
        s=np.arange(5, dtype=np.int32),
        aid=np.array([2 ** 40, -3], dtype=np.int64),
        flags=np.array([True, False]),
        nested=dict(hub=np.empty(0, dtype=np.int32), note=None),
        seq=[1, "two", 3.5])
    out = wire.decode(wire.encode(doc))
    assert out["method"] == "execute" and out["id"] == 7
    assert out["ok"] is True and out["nested"]["note"] is None
    for path, want in ((("s",), doc["s"]), (("aid",), doc["aid"]),
                       (("flags",), doc["flags"]),
                       (("nested", "hub"), doc["nested"]["hub"])):
        got = out
        for k in path:
            got = got[k]
        assert isinstance(got, np.ndarray)
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(got, want)
    assert list(out["seq"]) == [1, "two", 3.5]


def test_wire_codec_name_is_declared():
    assert wire.codec_name() in ("msgpack", "json")


def test_straggler_monitor_flags_outliers():
    mon = StragglerMonitor(window=8, factor=2.0)
    flagged = [mon.record(i, 0.1) for i in range(8)]
    assert not any(flagged)
    assert mon.record(9, 0.5) is True
    assert mon.record(10, 0.11) is False


# ------------------------------------------------------------------ #
# ShardWorker protocol (no processes)
# ------------------------------------------------------------------ #
def _worker_for(g, lo, hi, generation=0):
    idx = build_rlc_index(g, K)
    ids = mr_id_space(g.num_labels, K)
    frozen = idx.freeze(ids)
    id_to_mr = [mr for mr, _ in sorted(ids.items(), key=lambda kv: kv[1])]
    payload = _slice_payload(frozen.slice_rows(lo, hi), lo, hi,
                             generation, id_to_mr)
    w = ShardWorker("t0")
    reply = w.on_init(dict(payload, shard_id=0, replica_id=0))
    assert reply["generation"] == generation
    return w, frozen, idx, ids


def test_shard_worker_executes_its_slice():
    g = _graph(50, seed=3)
    w, frozen, idx, ids = _worker_for(g, 0, g.num_vertices)
    qs = _queries(g, 16, seed=1)
    s = np.array([q[0] for q in qs], dtype=np.int32)
    t = np.array([q[1] for q in qs], dtype=np.int32)
    mr = np.array([ids[q[2]] for q in qs], dtype=np.int32)
    reply, keep = w.handle(dict(method="execute", id=1,
                                s=s, t=t, mr=mr, n_real=len(s)))
    assert keep and reply["ok"]
    want = [idx.query(int(a), int(b), q[2])
            for a, b, q in zip(s, t, qs)]
    assert list(reply["ans"]) == want
    stats, _ = w.handle(dict(method="stats", id=2))
    assert stats["queries"] == len(qs) and stats["batches"] == 1


def test_shard_worker_rejects_unknown_method_and_stale_swap():
    g = _graph(40, seed=5)
    w, frozen, _idx, ids = _worker_for(g, 0, g.num_vertices, generation=3)
    reply, keep = w.handle(dict(method="frobnicate", id=9))
    assert keep and not reply["ok"] and "unknown method" in reply["error"]
    id_to_mr = [mr for mr, _ in sorted(ids.items(), key=lambda kv: kv[1])]
    stale = _slice_payload(frozen.slice_rows(0, g.num_vertices), 0,
                           g.num_vertices, 1, id_to_mr)
    reply, keep = w.handle(dict(stale, method="swap", id=10))
    assert keep and not reply["ok"] and "stale swap" in reply["error"]
    assert w.generation == 3 and w.swaps == 0
    fresh = _slice_payload(frozen.slice_rows(0, g.num_vertices), 0,
                           g.num_vertices, 4, id_to_mr)
    reply, keep = w.handle(dict(fresh, method="swap", id=11))
    assert reply["ok"] and w.generation == 4 and w.swaps == 1


def test_shard_worker_digest_hop_matches_direct_execution():
    g = _graph(60, seed=7)
    mid = g.num_vertices // 2
    w_lo, frozen, idx, ids = _worker_for(g, 0, mid)
    w_hi = ShardWorker("t1")
    id_to_mr = [mr for mr, _ in sorted(ids.items(), key=lambda kv: kv[1])]
    w_hi.on_init(dict(_slice_payload(frozen.slice_rows(mid,
                                                       g.num_vertices),
                                     mid, g.num_vertices, 0, id_to_mr),
                      shard_id=1, replica_id=0))
    # cross-shard queries: s on the low shard, t on the high shard
    qs = [(s, t, mr) for s, t, mr in _queries(g, 24, seed=2)
          if s < mid <= t]
    assert qs, "need at least one genuinely cross-shard query"
    s = np.array([q[0] for q in qs], dtype=np.int64)
    dig, _ = w_lo.handle(dict(method="gather_digest", id=1, s=s))
    assert dig["ok"]
    join, _ = w_hi.handle(dict(
        method="join_digest", id=2, s=s,
        t=np.array([q[1] for q in qs], dtype=np.int64),
        mr=np.array([ids[q[2]] for q in qs], dtype=np.int64),
        digest_indptr=dig["indptr"], digest_hub=dig["hub"],
        digest_mr=dig["mr"]))
    assert join["ok"]
    want = [idx.query(int(a), int(b), mr) for a, b, mr in qs]
    assert list(join["ans"]) == want
    assert w_lo.digests == len(qs) and w_hi.joins == len(qs)


# ------------------------------------------------------------------ #
# Spawn-based cluster (env-gated: RLC_RPC_WORKERS)
# ------------------------------------------------------------------ #
def test_rpc_cluster_matches_single_process():
    g = _graph()
    qs = _queries(g)
    single = _single(g)
    want = _bools(single.query_batch(qs))
    single.close()
    svc = _rpc(g)
    try:
        got = svc.query_batch(qs)
        assert _bools(got) == want
        backends = {a.backend for a in got}
        assert backends <= {"rpc:numpy", "rpc:sorted", "rpc:python",
                            "rpc:digest"}
        if svc.config.num_shards > 1:
            assert "rpc:digest" in backends, "no cross-shard query ran"
        st = validate_stats(svc.stats())
        assert st["transport"] == "rpc"
        assert st["rpc"]["live_workers"] == \
            svc.config.num_shards * svc.config.num_replicas
        assert st["rpc"]["wire_bytes"]["sent"] > 0
        assert st["rpc"]["wire_bytes"]["received"] > 0
        # cached re-ask never goes back over the wire
        again = svc.query_batch(qs)
        assert {a.disposition for a in again} == {"cache_hit"}
    finally:
        svc.close()
    assert all(not h.proc.is_alive()
               for hs in svc.cluster.handles.values() for h in hs)


def test_rpc_async_submit_with_mid_stream_swap():
    g = _graph(seed=13)
    qs = _queries(g, 32, seed=4)
    single = _single(g)
    want = _bools(single.query_batch(qs))
    single.close()
    svc = _rpc(g)
    try:
        with svc.start():
            futs = [svc.submit(s, t, c) for s, t, c in qs[:16]]
            swapped = svc.hot_swap()
            futs += [svc.submit(s, t, c) for s, t, c in qs[16:]]
            svc._engine.flush()
            got = [f.result(timeout=60) for f in futs]
        assert _bools(got) == want
        assert swapped >= 1
        assert svc.stats()["rpc"]["generation"] == svc.generation
    finally:
        svc.close()


def test_rpc_apply_delta_matches_reference():
    g = _graph(60, seed=17)
    svc = _rpc(g, delta_fallback_frac=1.0)
    rng = np.random.default_rng(23)
    try:
        for _ in range(2):
            delta = random_delta(svc.graph, 2, 2, rng)
            svc.apply_delta(delta)
            qs = _queries(svc.graph, 24, seed=int(rng.integers(1 << 30)))
            got = svc.query_batch(qs)
            ref = build_rlc_index(svc.graph, K, backend="python")
            want = [ref.query(s, t, mr) for s, t, mr in qs]
            assert _bools(got) == want
        assert svc.deltas_applied == 2
    finally:
        svc.close()


def test_rpc_worker_death_fails_over_to_sibling_replica():
    g = _graph(seed=19)
    qs = _queries(g, 30, seed=6)
    single = _single(g)
    want = _bools(single.query_batch(qs))
    single.close()
    svc = _rpc(g, num_shards=1, num_replicas=2)
    try:
        victim = svc.cluster.handles[0][0]
        victim.proc.terminate()
        victim.proc.join(timeout=10)
        got = svc.query_batch(qs)
        assert _bools(got) == want
        st = svc.stats()["rpc"]
        assert st["live_workers"] == 1
        assert st["retries"] >= 1
        assert not victim.alive
    finally:
        svc.close()


def test_rpc_worker_leave_and_rejoin_mid_stream():
    g = _graph(seed=29)
    qs = _queries(g, 30, seed=8)
    single = _single(g)
    want = _bools(single.query_batch(qs))
    single.close()
    svc = _rpc(g, num_shards=min(WORKERS, 2), num_replicas=1)
    try:
        base = svc.query_batch(qs)
        assert _bools(base) == want
        svc.cluster.leave(0, 0)
        svc.cache.clear()
        degraded = svc.query_batch(qs)
        assert _bools(degraded) == want, \
            "answers must stay exact while shard 0 has no workers"
        assert any(a.disposition == "degraded" for a in degraded), \
            "losing every replica of a shard must surface as degraded"
        svc.cluster.rejoin(0, 0)
        svc.cache.clear()
        healed = svc.query_batch(qs)
        assert _bools(healed) == want
        assert all(a.disposition != "degraded" for a in healed)
        st = validate_stats(svc.stats())["rpc"]
        assert st["leaves"] == 1 and st["rejoins"] == 1
        assert st["membership_epoch"] >= 2
    finally:
        svc.close()


@pytest.mark.slow
def test_rpc_bit_identical_sweep_shards_by_replicas():
    """The acceptance sweep: shards {1,2,4} x replicas {1,2}, each cell
    bit-identical to the single-process service, including a mid-stream
    hot swap."""
    g = _graph(100, seed=31)
    qs = _queries(g, 60, seed=9)
    single = _single(g)
    want = _bools(single.query_batch(qs))
    single.close()
    for num_shards in (1, 2, 4):
        for num_replicas in (1, 2):
            svc = _rpc(g, num_shards=num_shards,
                       num_replicas=num_replicas)
            try:
                assert _bools(svc.query_batch(qs)) == want, \
                    f"shards={num_shards} replicas={num_replicas}"
                svc.hot_swap()
                svc.cache.clear()
                assert _bools(svc.query_batch(qs)) == want, \
                    f"post-swap shards={num_shards} " \
                    f"replicas={num_replicas}"
                validate_stats(svc.stats())
            finally:
                svc.close()
