"""The one traffic generator: a pool of distinct queries, true and false
in shares the traffic file sets, and the order in which a run sends them.

Everything is drawn from the run's seed through its own named stream, so
the same seed gives the same pool and order, and a change to one stream
leaves the others as they were. Every seed gets the same amount of work:
the pool size and the share of each half come from the traffic file,
only which queries and in what order come from the seed.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

from .graph import out_csr
from .reference import Reference

#: stream ids under the run's seed (0 renames the graph's vertices)
POOL, ORDER, SAMPLE, WARM = range(1, 5)


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
