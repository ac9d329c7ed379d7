"""Cross-engine agreement property test.

On small random labeled graphs, the dict-layout index (`RLCIndex.query`),
the frozen-numpy CSR merge-join (`FrozenRLCIndex.query_batch`), the padded
device layout in both formulations (XLA sorted-key and the dense reference)
and the full `RLCService` path must all agree with the product-automaton
BiBFS oracle on the same query set — >= 200 queries across >= 3 graphs
(ISSUE-1 acceptance)."""
import functools

import numpy as np
import pytest

from repro.core.baselines import bibfs_rlc
from repro.core.device_index import DeviceIndex
from repro.core.index_builder import build_rlc_index
from repro.core.minimum_repeat import enumerate_mrs, mr_id_space
from repro.core.queries import biased_true_queries
from repro.graphgen import (barabasi_albert, erdos_renyi,
                            random_labeled_graph)
from repro.service import (RLCService, ServiceConfig, ShardedRLCService,
                           ShardedServiceConfig)

GRAPHS = [
    ("er", lambda: erdos_renyi(30, 3.0, 3, seed=11)),
    ("ba", lambda: barabasi_albert(24, 2, 3, seed=12)),
    ("loopy", lambda: random_labeled_graph(20, 70, 2, seed=13,
                                           self_loop_frac=0.2)),
    ("sparse", lambda: erdos_renyi(40, 1.5, 4, seed=14)),
]
PER_GRAPH = 80  # x 4 graphs = 320 queries >= the 200-query acceptance bar


@pytest.mark.parametrize("name,make", GRAPHS, ids=[g[0] for g in GRAPHS])
def test_cross_engine_agreement(name, make):
    g = make()
    k = 2
    idx = build_rlc_index(g, k)
    ids = mr_id_space(g.num_labels, k)
    frozen = idx.freeze(ids)
    dev = DeviceIndex.from_frozen(frozen, ids)
    svc = RLCService.build(g, ServiceConfig(k=k, batch_size=16,
                                            cache_capacity=128), index=idx)

    rng = np.random.default_rng(hash(name) % 2**31)
    mrs = enumerate_mrs(g.num_labels, k)
    queries = [(int(rng.integers(g.num_vertices)),
                int(rng.integers(g.num_vertices)),
                mrs[int(rng.integers(len(mrs)))]) for _ in range(PER_GRAPH)]
    want = [bibfs_rlc(g, s, t, L) for s, t, L in queries]

    s = np.array([q[0] for q in queries], np.int32)
    t = np.array([q[1] for q in queries], np.int32)
    mid = np.array([ids[q[2]] for q in queries], np.int32)

    # 1. dict layout (Algorithm 1 over hash maps)
    got_dict = [idx.query(*q) for q in queries]
    assert got_dict == want

    # 2. frozen-numpy CSR merge join
    got_np = frozen.query_batch(s, t, mid)
    np.testing.assert_array_equal(got_np, np.asarray(want))

    # 3. device layout, sorted-key XLA formulation
    got_sorted = dev.query_batch(s, t, mid, method="sorted")
    np.testing.assert_array_equal(got_sorted, np.asarray(want))

    # 4. device layout, dense reference formulation
    got_dense = dev.query_batch(s, t, mid, method="dense")
    np.testing.assert_array_equal(got_dense, np.asarray(want))

    # 5. the full service path (cache + scheduler + executor)
    got_svc = svc.query_batch(queries)
    assert got_svc == want


# ------------------------------------------------------------------ #
# The served path, per backend and MR length, against the oracle
# ------------------------------------------------------------------ #
BACKENDS = ["pallas", "sorted", "numpy", "python"]


@functools.lru_cache(maxsize=None)
def _served_case(name, k):
    """Graph, index and a true/false query mix with the BiBFS oracle's
    answers, once per (graph, k) for every backend that serves it."""
    g = dict(GRAPHS)[name]()
    qs = biased_true_queries(g, k, n=24, seed=k)
    rng = np.random.default_rng(k)
    mrs = enumerate_mrs(g.num_labels, k)
    queries = qs.true_queries + qs.false_queries + [
        (int(rng.integers(g.num_vertices)), int(rng.integers(g.num_vertices)),
         mrs[int(rng.integers(len(mrs)))]) for _ in range(24)]
    want = [bibfs_rlc(g, s, t, L) for s, t, L in queries]
    assert any(want) and not all(want)
    assert max(len(L) for _, _, L in queries) == k
    return g, build_rlc_index(g, k), queries, want


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("k", [1, 2, 3])
@pytest.mark.parametrize("name", [g[0] for g in GRAPHS])
def test_served_backend_matches_oracle(name, k, backend):
    """``RLCService.query_batch`` on one backend answers as BiBFS does,
    for every MR length up to ``k``."""
    g, idx, queries, want = _served_case(name, k)
    svc = RLCService.build(g, ServiceConfig(
        k=k, batch_size=16, cache_capacity=0, backend=backend), index=idx)
    got = svc.query_batch(queries)
    assert [a.value for a in got] == want
    assert {a.backend for a in got} == {backend}


@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("name", [g[0] for g in GRAPHS])
def test_sharded_service_matches_oracle_at_k(name, k):
    """The sharded facade (frozen row slices per shard, cross-shard
    digest joins on the host) at MR lengths other than 2."""
    g, idx, queries, want = _served_case(name, k)
    svc = ShardedRLCService.build(g, ShardedServiceConfig(
        k=k, batch_size=16, cache_capacity=0, num_shards=2,
        use_device=False), index=idx)
    assert svc.query_batch(queries) == want
    assert svc.router.stats()["remote"] > 0
