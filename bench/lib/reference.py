"""The plain reference: RLC reachability by breadth-first search.

``s ~L+~> t`` holds when some non-empty path from ``s`` to ``t`` spells
``L`` repeated one or more times. The search runs over the product of the
graph with the cycle automaton of ``L``: state ``(v, i)`` means "at ``v``,
having read ``i`` labels of the current repetition", and an edge ``u -a->
w`` moves ``(u, i)`` to ``(w, (i + 1) mod |L|)`` when ``a == L[i]``. The
query is true when a predecessor of ``(t, 0)`` is reachable from
``(s, 0)``, which makes the path non-empty and so also covers ``s == t``.

It walks the edge list it is given and reads nothing of the index.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import breadth_first_order


class Reference:
    def __init__(self, num_vertices: int, edges: np.ndarray):
        self.n = int(num_vertices)
        self.edges = np.asarray(edges, np.int64)
        self._product: Dict[Tuple[int, ...], tuple] = {}

    def _graph(self, L: Tuple[int, ...]):
        g = self._product.get(L)
        if g is None:
            m = len(L)
            src, lab, dst = self.edges.T
            rows, cols = [], []
            for i, a in enumerate(L):
                sel = lab == a
                rows.append(src[sel] * m + i)
                cols.append(dst[sel] * m + (i + 1) % m)
            rows = np.concatenate(rows)
            cols = np.concatenate(cols)
            fwd = csr_matrix((np.ones(len(rows), np.int8), (rows, cols)),
                             shape=(self.n * m, self.n * m))
            g = (fwd, fwd.T.tocsr())
            self._product[L] = g
        return g

    def reached(self, s: int, L: Tuple[int, ...]) -> np.ndarray:
        """Boolean mask of the product states reachable from ``(s, 0)``."""
        fwd, _ = self._graph(L)
        mask = np.zeros(fwd.shape[0], bool)
        mask[breadth_first_order(fwd, s * len(L), directed=True,
                                 return_predecessors=False)] = True
        return mask

    def answer_from(self, mask: np.ndarray, t: int,
                    L: Tuple[int, ...]) -> bool:
        _, bwd = self._graph(L)
        state = t * len(L)
        preds = bwd.indices[bwd.indptr[state]:bwd.indptr[state + 1]]
        return bool(mask[preds].any())

    def targets(self, s: int, L: Tuple[int, ...]) -> np.ndarray:
        """The answer of ``(s, t, L)`` for every vertex ``t``: one search,
        then the targets with a reached predecessor of ``(t, 0)``."""
        _, bwd = self._graph(L)
        hit = bwd @ self.reached(s, L).astype(np.int32)
        return hit[::len(L)] > 0

    def answers(self, queries: Sequence[Tuple[int, int, Tuple[int, ...]]]
                ) -> np.ndarray:
        """One answer per ``(s, t, L)``; one search per distinct
        ``(s, L)``."""
        out = np.zeros(len(queries), bool)
        by_source: Dict[tuple, list] = {}
        for i, (s, t, L) in enumerate(queries):
            by_source.setdefault((int(s), tuple(L)), []).append((i, int(t)))
        for (s, L), items in by_source.items():
            mask = self.reached(s, L)
            for i, t in items:
                out[i] = self.answer_from(mask, t, L)
        return out
