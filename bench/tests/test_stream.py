"""Cells whose traffic writes to the graph: the write-aware answer check,
the write stream, and generators found by name."""
import contextlib
import hashlib
import io
import time

import numpy as np
import pytest

from bench.lib import cell as cl
from bench.lib import graph as gr
from bench.lib import traffic as tf
from bench.lib.reference import Reference


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    import repro.device
    monkeypatch.setattr(repro.device, "enable_compile_cache", lambda: "")


def _check(run):
    err = io.StringIO()
    with contextlib.redirect_stderr(err):
        check = cl.check_answers(run)
    return check, err.getvalue()


def test_a_run_without_writes_checks_as_before():
    """``ep-batch`` on seed 1: the picks, the numbers and the printed line
    of the check as it was before writes were checked. The window is
    made by hand: a cycle of the pool and 20,000 more, every 997th
    answer inverted."""
    bench = cl.load_json(cl.ROOT / "BENCHMARK.json")
    cell = cl.Cell.from_benchmark(bench, "ep-batch", False)
    cfg = cell.config
    edges = gr.make_edges(cfg, 1)
    pool = tf.make_pool(cfg["vertices"], edges, cfg["k"],
                        cell.traffic["pool"], cell.traffic["walk_share"],
                        tf.stream(1, tf.POOL))
    order = tf.stream(1, tf.ORDER).permutation(len(pool))
    served = np.concatenate([order, order[:20000]])
    answers = served < pool.n_walk
    answers[::997] = ~answers[::997]
    run = cl.Run(cell, 1, 30.0, pool=pool, edges=edges, served=served,
                 answers=answers, attempted=len(served),
                 answered=len(served))
    check, said = _check(run)
    assert check == dict(wrong=dict(value=4, limit=0),
                         unanswered=dict(value=0, limit=0),
                         failed=dict(value=0, limit=0),
                         compared_none=dict(value=0, limit=0))
    assert said == ("compared 2671 answers to 2048 distinct queries with "
                    "the reference; reference says true for 1008 of 1008 "
                    "from walks, 0 of 1040 drawn false\n")


#: 0 -a-> 1; the write inserts 1 -a-> 2, so (0, 2, (a)+) turns true
EDGES = np.array([[0, 0, 1]], np.int32)
WRITE = (np.array([[1, 0, 2]], np.int32), np.zeros((0, 3), np.int32))
MRS = tf.constraints(2, 2)


def _written(answers, served=(0, 1, 0, 1), epochs=(0, 0, 1, 1),
             flips=(0,), writes=(WRITE,), due=1):
    """A run on ``EDGES`` whose pool is ``(0, 2, a)``, which the write
    changes, and ``(0, 1, a)``, which it does not."""
    a = MRS.index((0,))
    pool = tf.Pool(np.array([0, 0]), np.array([2, 1]), np.array([a, a]),
                   MRS, 1)
    cell = cl.Cell("tiny", 1, dict(vertices=3), {}, [])
    served = np.asarray(served)
    return cl.Run(cell, 5, 1.0, pool=pool, edges=EDGES, served=served,
                  answers=np.asarray(answers, bool),
                  epochs=np.asarray(epochs), flips=np.asarray(flips, int),
                  writes=list(writes), writes_due=due,
                  attempted=len(served), answered=len(served))


@pytest.mark.parametrize("after, wrong", [(False, 1), (True, 0)],
                         ids=["stale", "fresh"])
def test_each_answer_is_held_to_the_graph_of_its_call(after, wrong):
    check, said = _check(_written([False, True, after, True]))
    assert check["wrong"] == dict(value=wrong, limit=0)
    assert check["after_write_none"]["value"] == 0
    assert check["flips_none"]["value"] == 0
    assert f"2 answers to 1 distinct queries that a write changes, " \
           f"{wrong} wrong" in said


@pytest.mark.parametrize("entry, run", [
    ("writes_missing", dict(due=2)),
    ("after_write_none", dict(served=(0, 1), epochs=(0, 0))),
    ("flips_none", dict(flips=())),
], ids=["writes_missing", "after_write_none", "flips_none"])
def test_write_check_entries_fire(entry, run):
    kw = dict(served=(0, 1, 0, 1), epochs=(0, 0, 1, 1))
    kw.update(run)
    answers = [False, True, True, True][:len(kw["served"])]
    check, _ = _check(_written(answers, **kw))
    assert check[entry] == dict(value=1, limit=0)
    assert check["wrong"]["value"] == 0
    others = {"writes_missing", "after_write_none", "flips_none"} - {entry}
    assert all(check[o]["value"] == 0 for o in others)


MIX = cl.load_json(cl.BENCH / "tests" / "fixtures" / "write-stream.json")


def _stream(seed, count=6, vertices=600):
    cfg = dict(cl.load_json(cl.BENCH / "configs" / "ba-ep-4k.json"),
               vertices=vertices)
    edges = gr.make_edges(cfg, seed)
    mrs = tf.constraints(cfg["labels"], cfg["k"])
    return cfg, edges, mrs, tf.run_writes(edges, cfg, MIX, seed, mrs, count)


def test_every_seed_gets_the_same_writes_renamed():
    cfg, _, _, a = _stream(3)
    *_, b = _stream(2**31 + 7)
    back = []
    for seed, writes in ((3, a), (2**31 + 7, b)):
        inv = np.argsort(gr.renaming(cfg["vertices"], seed))
        back.append([(w.kind, w.renamed(inv).row, w.renamed(inv).flips,
                      w.renamed(inv).probes) for w in writes])
    assert back[0] == back[1]
    assert [w.row for w in a] != [w.row for w in b]


@pytest.mark.parametrize("seconds, count", [(0.01, 1), (0.5, 4), (30, 240)])
def test_the_number_of_writes_follows_the_rate(seconds, count):
    assert tf.write_count(MIX, seconds) == count


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_writes_keep_the_mix_and_carry_their_queries(seed):
    cfg, edges, mrs, writes = _stream(seed)
    kinds = [w.kind for w in writes]
    assert kinds.count("insert") == round(MIX["insert_share"] * 6)
    assert kinds.count("delete") == 6 - kinds.count("insert")
    n = cfg["vertices"]
    for w in writes:
        after = gr.apply_write(edges, *w.rows())
        have = set(map(tuple, edges.tolist()))
        assert (w.row in have) == (w.kind == "delete")
        assert len(after) == len(edges) + (1 if w.kind == "insert" else -1)
        u, a, v = w.row
        if (u, v, mrs.index((a,))) in w.flips:
            assert w.flips[0] == (u, v, mrs.index((a,)))
        assert len(w.flips) <= MIX["flips_per_write"]
        qs = [(s, t, mrs[m]) for s, t, m in w.flips]
        assert (Reference(n, edges).answers(qs)
                != Reference(n, after).answers(qs)).all()
        near = {u} | {x for x, _, y in after.tolist() if y == u} | {
            s for s, _, _ in w.flips}
        assert 0 < len(w.probes) <= MIX["probes_per_write"]
        assert len(set(w.probes)) == len(w.probes)
        assert not set(w.probes) & set(w.flips)
        assert {s for s, _, _ in w.probes} <= near
        edges = after
    assert any(w.flips for w in writes)


def test_an_insert_is_drawn_as_the_generator_attaches():
    """Inserts are not chosen for what they change: some change no
    answer that the search finds."""
    cfg = dict(cl.load_json(cl.BENCH / "configs" / "ba-ep-4k.json"),
               vertices=600)
    edges = gr.make_edges(cfg, 1)
    mrs = tf.constraints(cfg["labels"], cfg["k"])
    writes = tf.draw_writes(600, edges, ["insert"] * 40, mrs, 2.0, 16, 4,
                            np.random.default_rng(9))
    assert {w.row[1] for w in writes} >= {0, 1}
    assert not all(w.flips for w in writes)


@pytest.mark.parametrize("name, seed, digest", [
    ("ba-ep-4k", 1, "41d2a9b935f58544b57e3236e33bfdfc"
                    "df7e484b51555d049ea21cf224e35204"),
    ("ba-ep-4k", 2**31 + 5, "e6a5eb71545d461ef0561f8fcd646f02"
                            "032bffd0a54cf8f789a767f8a62eff04"),
    ("ba-ad", 1, "2e14dfc48d3d89e49befaa89f7aa3b83"
                 "8ee0f966a02c4c308f56e7aa8b0e8a43"),
    ("ba-ad", 2**31 + 5, "c8469cb0cc443f43dfdfe12b9c4a1d1c"
                         "bf5027a69dcb1ba9e6854c1d0117e09e"),
])
def test_configurations_draw_the_same_edges_as_before(name, seed, digest):
    cfg = cl.load_json(cl.BENCH / "configs" / f"{name}.json")
    edges = gr.make_edges(cfg, seed)
    assert edges.dtype == np.int32 and edges.shape[1] == 3
    assert hashlib.sha256(edges.tobytes()).hexdigest() == digest


def test_a_generator_is_found_by_name(tmp_path, monkeypatch):
    (tmp_path / "ring.py").write_text(
        "import numpy as np\n"
        "def make_edges(config, seed):\n"
        "    n = config['vertices']\n"
        "    v = np.arange(n)\n"
        "    return np.stack([v, v % config['labels'], (v + seed) % n], 1)\n")
    monkeypatch.setattr(gr, "GRAPHS", tmp_path)
    cfg = dict(generator="ring", vertices=10, labels=3, graph_seed=1)
    drawn = gr.base_edges(cfg)
    assert drawn.dtype == np.int32
    assert sorted(map(tuple, drawn.tolist())) == [
        (v, v % 3, (v + 1) % 10) for v in range(10)]
    perm = gr.renaming(10, 2**31 + 3)
    assert np.array_equal(gr.make_edges(cfg, 2**31 + 3),
                          gr.rename(drawn, perm))
    with pytest.raises(ValueError, match="no file .*missing.py"):
        gr.base_edges(dict(cfg, generator="missing"))


def test_a_traced_write_run_reports_the_delta_layers(tmp_path):
    from bench.tests.test_correctness import tiny
    out = cl.execute(tiny("write-stream", trace=True), 2**31 + 78, 0.5,
                     True, time.perf_counter(), require_chip=False,
                     log_dir=str(tmp_path))
    assert out["correct"], out["check"]
    m = out["metrics"]
    assert set(m) == {"layout_s", "delta_fallback_pct", "delta_build_ms",
                      "delta_rest_ms"}
    assert 0 <= m["delta_fallback_pct"]["value"] <= 100
    assert m["delta_build_ms"]["value"] > 0 and m["delta_rest_ms"]["value"] > 0


def test_drawing_the_writes_is_kept_out_of_setup():
    from bench.tests.test_correctness import tiny
    seen = {}
    out = cl.execute(tiny("write-stream"), 2**31 + 79, 0.5, False,
                     time.perf_counter(), require_chip=False,
                     hook=lambda run: seen.setdefault("run", run))
    run = seen["run"]
    assert out["correct"], out["check"]
    assert run.reference_s > 0
    assert len(run.probes) > 0 and not np.isin(run.probes, run.flips).any()
