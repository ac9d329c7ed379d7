"""The traffic generator: everything from the seed, the same amount of
work for every seed, and the batch mix's reuse distance against the
result cache."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.lib import graph as gr
from bench.lib import traffic as tf

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
SEEDS = [3, 2**31 + 11, 2**40 + 5]


def _pool(seed, size=2048, n=600):
    edges = gr.barabasi_albert(n, 3, 4, seed)
    return edges, tf.make_pool(n, edges, 2, size, 0.5,
                               tf.stream(seed, tf.POOL))


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_inputs(seed):
    e1, p1 = _pool(seed)
    e2, p2 = _pool(seed)
    assert np.array_equal(e1, e2)
    for a, b in ((p1.s, p2.s), (p1.t, p2.t), (p1.mr, p2.mr)):
        assert np.array_equal(a, b)
    o1 = tf.stream(seed, tf.ORDER).permutation(len(p1))
    o2 = tf.stream(seed, tf.ORDER).permutation(len(p2))
    assert np.array_equal(o1, o2)


def test_other_seed_other_inputs():
    _, p1 = _pool(SEEDS[0])
    _, p2 = _pool(SEEDS[1])
    assert not np.array_equal(p1.s, p2.s)


@pytest.mark.parametrize("seed", SEEDS)
def test_pool_is_distinct_and_of_its_size(seed):
    _, p = _pool(seed)
    keys = set(zip(p.s.tolist(), p.t.tolist(), p.mr.tolist()))
    assert len(p) == 2048 == len(keys)
    assert p.n_walk == 1024
    assert all(tf.primitive(p.mrs[m]) for m in set(p.mr.tolist()))


def test_batch_mix_reuse_distance_exceeds_the_cache():
    """The closed loop cycles one permutation of the pool: each query
    comes back after exactly ``pool`` others, far above the LRU's
    capacity, so the cache cannot answer it."""
    from repro.service import ServiceConfig
    mix = json.loads((TRAFFIC / "batch-shuffled.json").read_text())
    capacity = ServiceConfig(**mix.get("service", {})).cache_capacity
    order = tf.stream(9, tf.ORDER).permutation(mix["pool"])
    stream = np.concatenate([order, order])
    last = {}
    dist = []
    for i, q in enumerate(stream.tolist()):
        if q in last:
            dist.append(i - last[q])
        last[q] = i
    assert min(dist) == mix["pool"] > 8 * capacity


@pytest.mark.parametrize("seq, prim, mr", [
    ((1,), True, (1,)), ((1, 2), True, (1, 2)), ((2, 2), False, (2,)),
    ((1, 2, 1, 2), False, (1, 2)), ((1, 2, 1), True, (1, 2, 1))])
def test_primitive_and_minimum_repeat(seq, prim, mr):
    assert tf.primitive(seq) is prim
    assert tf.minimum_repeat(seq) == mr


def test_constraints_are_the_indexed_minimum_repeats():
    from repro.core.minimum_repeat import mr_id_space
    assert set(tf.constraints(8, 2)) == set(mr_id_space(8, 2))
