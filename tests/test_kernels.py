"""Pallas kernels (interpret mode) vs pure-jnp oracles: shape/dtype sweeps."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.bitpack import pack_bits, unpack_bits


def rand_bool(rng, shape, density=0.2, dtype=np.float32):
    return (rng.random(shape) < density).astype(dtype)


# ------------------------------------------------------------------ #
# bool_semiring
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("m,k,n", [
    (128, 128, 128), (256, 128, 384), (64, 64, 64), (128, 256, 128),
    (100, 130, 90),        # ragged -> exercises padding
    (8, 8, 8),
])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_bool_matmul_matches_ref(m, k, n, dtype):
    rng = np.random.default_rng(m * 1000 + k * 10 + n)
    a = rand_bool(rng, (m, k)).astype(dtype)
    b = rand_bool(rng, (k, n)).astype(dtype)
    got = ops.bool_matmul(a, b, interpret=True)
    want = ref.bool_matmul_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("n", [64, 128, 200, 256])
def test_closure_step_matches_ref(n):
    rng = np.random.default_rng(n)
    r = rand_bool(rng, (n, n), density=0.05)
    got = ops.closure_step(jnp.asarray(r), interpret=True)
    want = ref.fused_closure_step_ref(jnp.asarray(r))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_closure_step_converges_to_transitive_closure():
    rng = np.random.default_rng(0)
    n = 96
    r = rand_bool(rng, (n, n), density=0.02)
    R = jnp.asarray(r)
    for _ in range(8):
        R = ops.closure_step(R, interpret=True)
    # fixpoint reached: R == R | R@R
    np.testing.assert_array_equal(
        np.asarray(R), np.asarray(ref.fused_closure_step_ref(R)))


# ------------------------------------------------------------------ #
# mergejoin
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("n,E,Q", [(32, 8, 17), (64, 24, 64), (128, 64, 3)])
def test_mergejoin_matches_ref(n, E, Q):
    rng = np.random.default_rng(n + E + Q)
    def rows():
        hub = rng.integers(-1, n, size=(n, E)).astype(np.int32)
        mr = rng.integers(0, 6, size=(n, E)).astype(np.int32)
        mr[hub == -1] = -1
        return jnp.asarray(hub), jnp.asarray(mr)
    oh, om = rows()
    ih, im = rows()
    s = jnp.asarray(rng.integers(0, n, Q).astype(np.int32))
    t = jnp.asarray(rng.integers(0, n, Q).astype(np.int32))
    mr = jnp.asarray(rng.integers(0, 6, Q).astype(np.int32))
    got = ops.mergejoin_query(oh, om, ih, im, s, t, mr, interpret=True)
    want = ref.mergejoin_ref(oh, om, ih, im, s, t, mr)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,base", [(13, 0), (13, 100), (21, 5), (3, 7)])
def test_mergejoin_row_window_matches_ref(n, base):
    """A shard's window: rows [base, base + n) with global ids, n not a
    multiple of the kernel's 8-row group (a partial last group)."""
    E, Q, V = 16, 24, base + n
    rng = np.random.default_rng(n * 31 + base)

    def rows():
        hub = np.full((V, E), -1, np.int32)
        mr = np.full((V, E), -1, np.int32)
        hub[base:] = rng.integers(-1, V, size=(n, E))
        mr[base:] = rng.integers(0, 4, size=(n, E))
        mr[hub == -1] = -1
        return hub, mr
    oh, om = rows()
    ih, im = rows()
    s = rng.integers(base, V, Q).astype(np.int32)
    t = rng.integers(base, V, Q).astype(np.int32)
    mr = rng.integers(0, 4, Q).astype(np.int32)
    got = ops.mergejoin_query(*(jnp.asarray(a[base:]) for a in (oh, om, ih,
                                                                im)),
                              s, t, mr, interpret=True, row_base_out=base,
                              row_base_in=base)
    want = ref.mergejoin_ref(*(jnp.asarray(a) for a in (oh, om, ih, im, s, t,
                                                        mr)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mergejoin_on_real_index():
    from repro.core.device_index import DeviceIndex
    from repro.core.index_builder import build_rlc_index
    from repro.core.minimum_repeat import mr_id_space
    from repro.graphgen import random_labeled_graph

    g = random_labeled_graph(num_vertices=12, num_edges=36, num_labels=2,
                             seed=0)
    idx = build_rlc_index(g, 2)
    dev = DeviceIndex.from_index(idx, g.num_labels)
    ids = mr_id_space(g.num_labels, 2)
    qs, qt, qm, want = [], [], [], []
    for s in range(12):
        for t in range(12):
            for L, c in ids.items():
                qs.append(s), qt.append(t), qm.append(c)
                want.append(idx.query(s, t, L))
    got = dev.query_batch(np.array(qs), np.array(qt), np.array(qm),
                          use_pallas=True)
    assert got.tolist() == want


# ------------------------------------------------------------------ #
# bitpack
# ------------------------------------------------------------------ #
def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(1)
    x = rand_bool(rng, (16, 256))
    xp = pack_bits(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(unpack_bits(xp)), x)


@pytest.mark.parametrize("m,k,n", [(64, 64, 1024), (128, 128, 4096),
                                   (32, 100, 512)])
def test_bitpack_matmul_matches_ref(m, k, n):
    rng = np.random.default_rng(m + k + n)
    a = rand_bool(rng, (m, k), density=0.15)
    b = rand_bool(rng, (k, n), density=0.15)
    bp = pack_bits(jnp.asarray(b))
    got = ops.bitpack_matmul(jnp.asarray(a), bp, interpret=True)
    # oracle: unpack(out) == bool_matmul(a, b)
    want = ref.bool_matmul_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(unpack_bits(got))[:, :n], np.asarray(want))


# ------------------------------------------------------------------ #
# label_frontier
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("B,V,L", [(128, 128, 3), (64, 200, 2),
                                   (32, 64, 5)])
def test_frontier_step_matches_ref(B, V, L):
    rng = np.random.default_rng(B + V + L)
    f = rand_bool(rng, (B, V), density=0.1)
    A = rand_bool(rng, (L, V, V), density=0.05)
    for lab in range(L):
        got = ops.frontier_step(jnp.asarray(f), jnp.asarray(A),
                                jnp.asarray(lab), interpret=True)
        want = ref.frontier_step_ref(jnp.asarray(f), jnp.asarray(A),
                                     jnp.asarray(lab))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------------------ #
# dense engine plumbed through the Pallas matmul
# ------------------------------------------------------------------ #
def test_dense_engine_with_pallas_matmul():
    from functools import partial
    from repro.core.dense import DenseEngine
    from repro.core.baselines import ETC
    from repro.graphgen import random_labeled_graph

    g = random_labeled_graph(num_vertices=10, num_edges=30, num_labels=2,
                             seed=6)
    mm = partial(ops.bool_matmul, interpret=True)
    eng = DenseEngine.build(g, 2, matmul=mm)
    etc = ETC(g, 2)
    for u in range(10):
        for v in range(10):
            assert eng.s_k(u, v) == etc.s_k(u, v)


# ------------------------------------------------------------------ #
# label_frontier: multi-label / multi-step batching
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("R,V,L", [(6, 128, 3), (9, 256, 4)])
def test_frontier_step_many_matches_per_row(R, V, L):
    from repro.kernels.label_frontier import frontier_step_many

    rng = np.random.default_rng(R + V + L)
    f = rand_bool(rng, (R, V), density=0.08)
    A = rand_bool(rng, (L, V, V), density=0.04)
    labels = rng.integers(0, L, R).astype(np.int32)
    got = frontier_step_many(jnp.asarray(f), jnp.asarray(A),
                             jnp.asarray(labels), interpret=True)
    want = np.stack([(f[r] @ A[labels[r]]) > 0 for r in range(R)])
    np.testing.assert_array_equal(np.asarray(got),
                                  want.astype(np.float32))


def test_frontier_steps_matches_chained_many():
    from repro.kernels.label_frontier import frontier_steps

    rng = np.random.default_rng(42)
    R, V, L, T = 5, 128, 3, 4
    f = rand_bool(rng, (R, V), density=0.08)
    A = rand_bool(rng, (L, V, V), density=0.04)
    labels = rng.integers(0, L, (T, R)).astype(np.int32)
    dst = np.stack([rng.permutation(R) for _ in range(T)]).astype(np.int32)
    got = frontier_steps(jnp.asarray(f), jnp.asarray(A),
                         jnp.asarray(labels), jnp.asarray(dst),
                         interpret=True)
    ref_f = f.copy()
    for t in range(T):
        step = np.stack([(ref_f[r] @ A[labels[t, r]]) > 0
                         for r in range(R)]).astype(np.float32)
        out = np.zeros_like(step)
        out[dst[t]] = step
        ref_f = out
    np.testing.assert_array_equal(np.asarray(got), ref_f)


def test_frontier_steps_advances_product_automaton():
    """frontier_steps with the cyclic phase shift == m scalar BFS waves
    of the kernel-BFS (no pruning) on a real graph."""
    from repro.graphgen import random_labeled_graph
    from repro.kernels.label_frontier import frontier_steps

    g = random_labeled_graph(num_vertices=20, num_edges=70, num_labels=2,
                             seed=1)
    V, Vp = g.num_vertices, 128
    A = np.zeros((2, Vp, Vp), np.float32)
    e = g.edges
    A[e[:, 1], e[:, 0], e[:, 2]] = 1
    Lseq = (0, 1)
    m = len(Lseq)
    # rows = phases; row p follows label L[p], result lands at (p+1) % m
    labels = np.tile([Lseq[p] for p in range(m)], (m, 1)).astype(np.int32)
    dst = np.tile((np.arange(m) + 1) % m, (m, 1)).astype(np.int32)
    F = np.zeros((m, Vp), np.float32)
    F[0, 3] = 1  # seed vertex 3 at phase 0
    got = np.asarray(frontier_steps(jnp.asarray(F), jnp.asarray(A),
                                    jnp.asarray(labels), jnp.asarray(dst),
                                    interpret=True))
    # scalar oracle: m unpruned product-automaton waves
    cur = {(3, 0)}
    for _ in range(m):
        nxt = set()
        for (x, p) in cur:
            for y in g.out_neighbors_with_label(x, Lseq[p]).tolist():
                nxt.add((y, (p + 1) % m))
        cur = nxt
    want = np.zeros((m, Vp), np.float32)
    for (y, p) in cur:
        want[p, y] = 1
    np.testing.assert_array_equal(got, want)
