"""The one traffic generator: a pool of distinct queries, true and false
in shares the traffic file sets, and the order in which a run sends them.

Everything is drawn from the run's seed through its own named stream, so
the same seed gives the same pool and order, and a change to one stream
leaves the others as they were. Every seed gets the same amount of work:
the pool size and the share of each half come from the traffic file,
only which queries and in what order come from the seed.

A mix that writes (``writes_per_s`` in its file) also gets one-edge
writes, as many as its rate puts in the window, drawn from the
configuration's ``graph_seed`` on the graph as drawn: every run of one
length applies the same writes to the same graph, renamed by its seed.
Each write carries the queries whose answer it changes and probes of the
index rows it most likely touches.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

from .graph import apply_write, out_csr, rename, renaming, zipf_labels
from .reference import Reference

#: stream ids under the run's seed (0 renames the graph's vertices)
POOL, ORDER, SAMPLE, WARM = range(1, 5)
#: stream id under the configuration's ``graph_seed``: its writes
WRITES = 5


def stream(seed: int, which: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, which]))


def primitive(seq: Tuple[int, ...]) -> bool:
    """True when ``seq`` is no power of a shorter sequence (its own
    minimum repeat): the constraints an RLC index answers."""
    n = len(seq)
    return all(seq != seq[:p] * (n // p) for p in range(1, n) if n % p == 0)


def minimum_repeat(seq: Tuple[int, ...]) -> Tuple[int, ...]:
    n = len(seq)
    for p in range(1, n + 1):
        if n % p == 0 and seq == seq[:p] * (n // p):
            return seq[:p]
    return seq


def constraints(num_labels: int, k: int) -> List[Tuple[int, ...]]:
    """Every primitive label sequence of length 1..k."""
    return [seq for n in range(1, k + 1)
            for seq in itertools.product(range(num_labels), repeat=n)
            if primitive(seq)]


#: false queries drawn from one search: targets that one (source,
#: constraint) does not reach
FALSE_PER_SEARCH = 32


@dataclass
class Pool:
    """``size`` distinct queries ``(s[i], t[i], mrs[mr[i]])``; the first
    ``n_walk`` are witnessed by a walk of the graph, so true, and the
    rest are false."""

    s: np.ndarray
    t: np.ndarray
    mr: np.ndarray
    mrs: List[Tuple[int, ...]]
    n_walk: int

    def __len__(self) -> int:
        return len(self.s)

    def query(self, i: int):
        return int(self.s[i]), int(self.t[i]), self.mrs[int(self.mr[i])]

    def queries(self, idx) -> list:
        return [self.query(i) for i in idx]

    def extended(self, queries) -> "Pool":
        """This pool with ``(s, t, mr)`` rows appended."""
        a = np.asarray(queries, np.int64).reshape(-1, 3)
        return Pool(np.concatenate([self.s, a[:, 0]]),
                    np.concatenate([self.t, a[:, 1]]),
                    np.concatenate([self.mr, a[:, 2]]), self.mrs,
                    self.n_walk)


def make_pool(num_vertices: int, edges: np.ndarray, k: int, size: int,
              walk_share: float, rng: np.random.Generator) -> Pool:
    """True and false queries, after the paper's query sets.

    A share ``walk_share`` of the pool comes from random walks of length
    1..k starting at the source of a random edge, each walk's label
    string reduced to its minimum repeat: the walk witnesses the answer
    true. The rest are false: a uniform source and a constraint drawn
    uniformly from those the walks saw, searched once with the plain
    reference, and up to :data:`FALSE_PER_SEARCH` uniform targets that
    the search does not reach. Queries are distinct across the pool."""
    num_labels = int(edges[:, 1].max()) + 1
    mrs = constraints(num_labels, k)
    mr_of = {m: i for i, m in enumerate(mrs)}
    indptr, lab, dst = out_csr(num_vertices, edges)
    deg = np.diff(indptr)
    seen = set()
    keys: List[Tuple[int, int, int]] = []
    n_walk = int(round(size * walk_share))
    while len(keys) < n_walk:
        before = len(keys)
        n = 2 * (n_walk - len(keys)) + 64
        e = rng.integers(len(edges), size=n)
        s = edges[e, 0]
        x = edges[e, 2]
        labels = [edges[e, 1]]
        length = rng.integers(1, k + 1, size=n)
        for step in range(1, k):
            go = (length > step) & (deg[x] > 0)
            j = indptr[x] + (rng.random(n) * np.maximum(deg[x], 1)
                             ).astype(np.int64)
            j = np.minimum(j, len(lab) - 1)
            labels.append(np.where(go, lab[j], -1))
            x = np.where(go, dst[j], x)
        for q in range(n):
            seq = tuple(int(a[q]) for a in labels if a[q] >= 0)
            key = (int(s[q]), int(x[q]), mr_of[minimum_repeat(seq)])
            if key not in seen and len(keys) < n_walk:
                seen.add(key)
                keys.append(key)
        if len(keys) - before < n // 1000:
            raise ValueError(f"walks of this graph give few distinct "
                             f"queries ({len(keys)} of {n_walk} wanted)")
    walk_mrs = sorted({m for _, _, m in keys})
    ref = Reference(num_vertices, edges)
    searches = 0
    while len(keys) < size:
        searches += 1
        if searches > 4 * size:
            raise ValueError(f"searches of this graph give few false "
                             f"queries ({len(keys) - n_walk} of "
                             f"{size - n_walk} wanted)")
        s = int(rng.integers(num_vertices))
        m = int(rng.choice(walk_mrs))
        unreached = np.flatnonzero(~ref.targets(s, mrs[m]))
        n = min(FALSE_PER_SEARCH, size - len(keys), len(unreached))
        for t in rng.choice(unreached, size=n, replace=False).tolist():
            if (s, t, m) not in seen:
                seen.add((s, t, m))
                keys.append((s, t, m))
    a = np.asarray(keys, np.int64)
    return Pool(a[:, 0], a[:, 1], a[:, 2], mrs, n_walk)




@dataclass
class Write:
    """One write of a stream: ``row`` ``(src, label, dst)`` inserted or
    deleted (``kind``); ``flips``, queries ``(s, t, mr)`` whose answer it
    changes; ``probes``, queries on the index rows it most likely
    touches, whatever their answer does."""

    kind: str
    row: Tuple[int, int, int]
    flips: List[Tuple[int, int, int]]
    probes: List[Tuple[int, int, int]]

    def rows(self) -> Tuple[np.ndarray, np.ndarray]:
        """``(inserts, deletes)``, each ``(m, 3)`` int32."""
        one = np.asarray([self.row], np.int32)
        none = np.zeros((0, 3), np.int32)
        return (one, none) if self.kind == "insert" else (none, one)

    def renamed(self, perm: np.ndarray) -> "Write":
        u, a, w = self.row
        return Write(self.kind, (int(perm[u]), a, int(perm[w])),
                     [(int(perm[s]), int(perm[t]), m)
                      for s, t, m in self.flips],
                     [(int(perm[s]), int(perm[t]), m)
                      for s, t, m in self.probes])


def write_count(mix: dict, seconds: float) -> int:
    """The writes a window of ``seconds`` holds at the mix's
    ``writes_per_s``; at least one."""
    return max(1, int(round(mix["writes_per_s"] * seconds)))


def run_writes(edges: np.ndarray, config: dict, mix: dict, seed: int,
               mrs: List[Tuple[int, ...]], count: int) -> List[Write]:
    """The first ``count`` writes of the mix for the run ``seed`` on its
    ``edges``: drawn from the configuration's ``graph_seed`` on the graph
    as drawn, then renamed as the run renames the graph. A share
    ``insert_share`` of them, rounded, are inserts, in an order drawn
    from the same stream; the rest are deletes."""
    perm = renaming(config["vertices"], seed)
    drawn = rename(edges, np.argsort(perm))
    rng = stream(config["graph_seed"], WRITES)
    inserts = int(round(mix["insert_share"] * count))
    kinds = rng.permutation(["insert"] * inserts
                            + ["delete"] * (count - inserts)).tolist()
    return [w.renamed(perm) for w in draw_writes(
        config["vertices"], drawn, kinds, mrs,
        config["label_zipf_exponent"], mix["flips_per_write"],
        mix["probes_per_write"], rng)]


def draw_writes(num_vertices: int, edges: np.ndarray, kinds: List[str],
                mrs: List[Tuple[int, ...]], label_exponent: float,
                flips_per_write: int, probes_per_write: int,
                rng: np.random.Generator) -> List[Write]:
    """One write of one edge per entry of ``kinds``, each drawn on the
    graph the writes before it left.

    An insert attaches as the Barabasi-Albert generator does: a uniform
    source, a target drawn by degree, a Zipf label; it is drawn again
    only while it is a loop or an edge already. A delete is uniform among
    the current edges. Each write carries its :func:`flips` and
    :func:`probes`."""
    num_labels = 1 + max(a for L in mrs for a in L)
    out = []
    cur = edges
    for kind in kinds:
        if kind == "insert":
            deg = (np.bincount(cur[:, 0], minlength=num_vertices)
                   + np.bincount(cur[:, 2], minlength=num_vertices))
            have = set(map(tuple, cur.tolist()))
            while True:
                u = int(rng.integers(num_vertices))
                w = int(rng.choice(num_vertices, p=deg / deg.sum()))
                a = int(zipf_labels(1, num_labels, rng, label_exponent)[0])
                if u != w and (u, a, w) not in have:
                    break
        elif kind == "delete":
            u, a, w = (int(x) for x in cur[rng.integers(len(cur))])
        else:
            raise ValueError(f"unknown write {kind!r}: insert or delete")
        write = Write(kind, (u, a, w), [], [])
        before = Reference(num_vertices, cur)
        after = Reference(num_vertices, apply_write(cur, *write.rows()))
        write.flips = flips(before, after, write.row, mrs, flips_per_write,
                            rng)
        write.probes = probes(after, write, mrs, probes_per_write, rng)
        out.append(write)
        cur = after.edges.astype(np.int32)
    return out


#: sources a flip search tries at most for one write: its own source and
#: vertices with an edge into it
FLIP_SOURCES = 32


def flips(before: Reference, after: Reference, row: Tuple[int, int, int],
          mrs: List[Tuple[int, ...]], limit: int,
          rng: np.random.Generator) -> List[Tuple[int, int, int]]:
    """Up to ``limit`` queries ``(s, t, mr)`` whose answer differs
    between ``before`` and ``after``, which differ in the edge ``row``
    alone. Searched from its source ``u``, then from vertices with an
    edge into ``u`` in an order drawn from ``rng``, :data:`FLIP_SOURCES`
    sources at most, under each constraint that holds its label;
    ``(u, w, (label))`` comes first where it changes. A write that
    changes no answer found so has none."""
    u, a, w = row
    both = np.concatenate([before.edges, after.edges])
    into = np.unique(both[both[:, 2] == u, 0])
    into = rng.permutation(into[into != u]).tolist()
    found: List[Tuple[int, int, int]] = []
    for s in [u] + into[:FLIP_SOURCES - 1]:
        for m, L in enumerate(mrs):
            if a in L:
                moved = before.targets(s, L) != after.targets(s, L)
                found += [(s, int(t), m) for t in np.flatnonzero(moved)]
        if len(found) >= limit:
            break
    first = (u, w, mrs.index((a,)))
    if first in found:
        found.remove(first)
        found.insert(0, first)
    return found[:limit]


def probes(after: Reference, write: Write, mrs: List[Tuple[int, ...]],
           limit: int, rng: np.random.Generator
           ) -> List[Tuple[int, int, int]]:
    """Up to ``limit`` distinct queries ``(s, t, mr)`` on the out-rows a
    write most likely patches, none of them among its flips: ``s`` is
    the write's source, a vertex with an edge into it, or the source of a
    flip; ``mr`` is uniform over all constraints, since a row holds
    entries of each; ``t`` is reached from ``s`` under ``mr`` after the
    write in half of them, uniform in the rest. They check the whole
    row, whether or not the write changed its answers."""
    u = write.row[0]
    e = after.edges
    near = np.unique(np.concatenate([[u], e[e[:, 2] == u, 0],
                                     [s for s, _, _ in write.flips]]))
    seen = set(write.flips)
    found: List[Tuple[int, int, int]] = []
    for i in range(4 * limit):
        if len(found) >= limit:
            break
        s = int(rng.choice(near))
        m = int(rng.integers(len(mrs)))
        reached = np.flatnonzero(after.targets(s, mrs[m]))
        t = int(rng.choice(reached) if i % 2 == 0 and len(reached)
                else rng.integers(after.n))
        if (s, t, m) not in seen:
            seen.add((s, t, m))
            found.append((s, t, m))
    return found
