"""The plain reference against the BiBFS oracle, and the benchmark's
copy of the graph generator against the program's."""
import json

import numpy as np
import pytest

from bench.lib import graph as gr
from bench.lib import traffic as tf
from bench.lib.reference import Reference


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 3])
def test_generator_copy_draws_the_program_graph(seed):
    from repro.graphgen import barabasi_albert
    want = barabasi_albert(300, 3, 4, seed=seed)
    got = gr.barabasi_albert(300, 3, 4, seed)
    assert np.array_equal(got, want.edges)


@pytest.mark.parametrize("seed,n,m,labels", [
    (0, 120, 2, 3), (1, 200, 3, 4), (2, 150, 1, 2), (3, 90, 4, 8)])
def test_reference_equals_bibfs(seed, n, m, labels):
    from repro.core.baselines import bibfs_rlc
    from repro.core.graph import LabeledGraph
    edges = gr.barabasi_albert(n, m, labels, seed)
    g = LabeledGraph.from_edges(n, labels, edges)
    rng = np.random.default_rng(seed)
    mrs = tf.constraints(labels, 2)
    qs = [(int(rng.integers(n)), int(rng.integers(n)),
           mrs[int(rng.integers(len(mrs)))]) for _ in range(300)]
    qs += [(s, s, L) for s, _, L in qs[:40]]          # cycles back to s
    pool = tf.make_pool(n, edges, 2, 200, 1.0, rng)    # walk-true
    qs += pool.queries(range(len(pool)))
    got = Reference(n, edges).answers(qs)
    want = [bibfs_rlc(g, s, t, L) for s, t, L in qs]
    assert got.tolist() == want
    assert got[-len(pool):].all()
    assert 0 < got.sum() < len(qs)


def test_every_seed_serves_the_same_graph_renamed():
    cfg = dict(generator="barabasi_albert", graph_seed=4, vertices=300,
               ba_m=3, ba_mirror_p=0.5, labels=4, label_zipf_exponent=2.0)
    a = gr.make_edges(cfg, 1)
    b = gr.make_edges(cfg, 2**31 + 1)
    assert len(a) == len(b) and not np.array_equal(a, b)
    assert np.array_equal(a, gr.make_edges(cfg, 1))
    for e in (a, b):                 # same degree and label profile
        assert sorted(np.bincount(e[:, 0], minlength=300)) == sorted(
            np.bincount(gr.barabasi_albert(300, 3, 4, 4)[:, 0],
                        minlength=300))
        assert np.array_equal(np.bincount(e[:, 1], minlength=4),
                              np.bincount(a[:, 1], minlength=4))


@pytest.mark.parametrize("name", ["ba-ep-4k", "ba-ad"])
def test_config_keeps_the_published_density(name):
    """A configuration cuts scale only: its graph has the label count and
    the edges per vertex of the graph it stands for."""
    from bench.lib.cell import BENCH
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    edges = gr.make_edges(cfg, 2**31 + 9)
    pub = cfg["published"]
    assert len(edges) / cfg["vertices"] == pytest.approx(
        pub["edges_per_vertex"], rel=0.01)
    assert cfg["labels"] == pub["labels"]
    assert set(np.unique(edges[:, 1]).tolist()) == set(range(cfg["labels"]))
    assert set(cfg["reduced"]) <= {"vertices"}


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_targets_answer_every_vertex(seed):
    edges = gr.barabasi_albert(150, 2, 3, seed)
    ref = Reference(150, edges)
    rng = np.random.default_rng(seed)
    for L in tf.constraints(3, 2)[:6]:
        s = int(rng.integers(150))
        want = ref.answers([(s, t, L) for t in range(150)])
        assert np.array_equal(ref.targets(s, L), want)


def test_pool_halves_are_true_and_false():
    edges = gr.barabasi_albert(400, 5, 3, 7, mirror_p=0.564)
    pool = tf.make_pool(400, edges, 2, 3000, 0.5, tf.stream(7, tf.POOL))
    got = Reference(400, edges).answers(pool.queries(range(len(pool))))
    assert got[:pool.n_walk].all() and not got[pool.n_walk:].any()
