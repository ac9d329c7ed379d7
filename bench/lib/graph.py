"""Seeded graph data for a configuration.

A copy of the Barabasi-Albert generator with Zipfian edge labels that the
paper's experiments use (section VI, after gMark), kept with the
benchmark so that the data a cell serves cannot change under a later PR.
It draws exactly what ``repro.graphgen.barabasi_albert`` draws for the
same seed, so the two give the same edges (a test holds them equal).

Any other generator a configuration names is the file
``bench/graphs/<generator>.py``, whose ``make_edges(config, seed)``
returns the ``(E, 3)`` int32 rows ``(src, label, dst)`` drawn from
``seed``: a new graph family arrives as a new file.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

#: where generators other than ``barabasi_albert`` are found, by name
GRAPHS = Path(__file__).resolve().parents[1] / "graphs"


def zipf_labels(num_edges: int, num_labels: int, rng: np.random.Generator,
                exponent: float) -> np.ndarray:
    ranks = np.arange(1, num_labels + 1, dtype=np.float64)
    p = ranks ** (-exponent)
    p /= p.sum()
    return rng.choice(num_labels, size=num_edges, p=p).astype(np.int32)


def barabasi_albert(num_vertices: int, m_attach: int, num_labels: int,
                    seed: int, label_exponent: float = 2.0,
                    mirror_p: float = 0.5) -> np.ndarray:
    """``(E, 3)`` int32 rows ``(src, label, dst)``, sorted and deduplicated.

    A complete directed core of ``m_attach + 1`` vertices; each later
    vertex sends ``m_attach`` edges to targets drawn by degree, and each
    such edge is mirrored with probability ``mirror_p``, which gives the
    cycles of the paper's social graphs. About ``m_attach * (1 +
    mirror_p)`` edges per vertex; the program's generator fixes
    ``mirror_p`` at 0.5.
    """
    rng = np.random.default_rng(seed)
    core = m_attach + 1
    src_l, dst_l = [], []
    for u in range(core):
        for v in range(core):
            if u != v:
                src_l.append(u)
                dst_l.append(v)
    degree = np.zeros(num_vertices, dtype=np.float64)
    degree[:core] = 2 * (core - 1)
    total = degree.sum()
    for v in range(core, num_vertices):
        p = degree[:v] / total
        targets = rng.choice(v, size=min(m_attach, v), replace=False, p=p)
        for t in targets:
            src_l.append(v)
            dst_l.append(int(t))
            if rng.random() < mirror_p:
                src_l.append(int(t))
                dst_l.append(v)
            degree[t] += 1
            degree[v] += 1
            total += 2
    lab = zipf_labels(len(src_l), num_labels, rng, label_exponent)
    edges = np.stack([np.asarray(src_l), lab, np.asarray(dst_l)], axis=1)
    return np.unique(edges.astype(np.int32), axis=0)


def base_edges(config: dict) -> np.ndarray:
    """The configuration's graph as drawn from its ``graph_seed``, before
    a run renames it."""
    gen = config["generator"]
    if gen == "barabasi_albert":
        return barabasi_albert(config["vertices"], config["ba_m"],
                               config["labels"], config["graph_seed"],
                               config["label_zipf_exponent"],
                               config["ba_mirror_p"])
    path = GRAPHS / f"{gen}.py"
    if not path.is_file():
        raise ValueError(f"unknown generator {gen!r}: no file {path}")
    from .cell import load_module
    edges = load_module(path).make_edges(config, config["graph_seed"])
    return np.unique(np.asarray(edges, np.int32).reshape(-1, 3), axis=0)


def renaming(num_vertices: int, seed: int) -> np.ndarray:
    """The permutation by which the run ``seed`` renames vertices."""
    return np.random.default_rng(np.random.SeedSequence([seed, 0])
                                 ).permutation(num_vertices)


def rename(rows: np.ndarray, perm: np.ndarray) -> np.ndarray:
    """``(src, label, dst)`` rows with both endpoints renamed by
    ``perm``, sorted and deduplicated."""
    out = np.array(rows, np.int32).reshape(-1, 3)
    out[:, 0] = perm[out[:, 0]]
    out[:, 2] = perm[out[:, 2]]
    return np.unique(out, axis=0)


def make_edges(config: dict, seed: int) -> np.ndarray:
    """The edges a configuration file describes, for the run ``seed``.

    The graph is drawn once from the configuration's ``graph_seed``, and
    ``seed`` renames its vertices by a permutation: every seed gets the
    same graph, so the same build and join work, in another order. (Each
    seed drawing its own graph moved the host build's time by up to 20%
    between seeds, against 2% between two runs of one seed.)
    """
    return rename(base_edges(config), renaming(config["vertices"], seed))


def apply_write(edges: np.ndarray, inserts: np.ndarray,
                deletes: np.ndarray) -> np.ndarray:
    """``(edges \\ deletes) | inserts``, sorted and deduplicated: the edge
    list after one write."""
    kept = edges
    if len(deletes):
        gone = np.isin(_keys(edges), _keys(np.asarray(deletes)))
        kept = edges[~gone]
    rows = np.concatenate([kept, np.asarray(inserts, np.int32)
                           .reshape(-1, 3)])
    return np.unique(rows.astype(np.int32), axis=0)


def _keys(rows: np.ndarray) -> np.ndarray:
    """One int64 key per ``(src, label, dst)`` row."""
    r = rows.astype(np.int64)
    return (r[:, 0] << 40) | (r[:, 1] << 32) | r[:, 2]


def out_csr(num_vertices: int, edges: np.ndarray):
    """``(indptr, label, dst)`` of the out-edges, by source."""
    order = np.argsort(edges[:, 0], kind="stable")
    e = edges[order]
    indptr = np.zeros(num_vertices + 1, np.int64)
    np.add.at(indptr, e[:, 0] + 1, 1)
    return np.cumsum(indptr), e[:, 1], e[:, 2]
