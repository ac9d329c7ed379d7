"""Seeded graph data for a configuration.

A copy of the Barabasi-Albert generator with Zipfian edge labels that the
paper's experiments use (section VI, after gMark), kept with the
benchmark so that the data a cell serves cannot change under a later PR.
It draws exactly what ``repro.graphgen.barabasi_albert`` draws for the
same seed, so the two give the same edges (a test holds them equal).
"""
from __future__ import annotations

import numpy as np


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


def make_edges(config: dict, seed: int) -> np.ndarray:
    """The edges a configuration file describes, for the run ``seed``.

    The graph is drawn once from the configuration's ``graph_seed``, and
    ``seed`` renames its vertices by a permutation: every seed gets the
    same graph, so the same build and join work, in another order. (Each
    seed drawing its own graph moved the host build's time by up to 20%
    between seeds, against 2% between two runs of one seed.)
    """
    if config["generator"] != "barabasi_albert":
        raise ValueError(f"unknown generator {config['generator']!r}")
    edges = barabasi_albert(config["vertices"], config["ba_m"],
                            config["labels"], config["graph_seed"],
                            config["label_zipf_exponent"],
                            config["ba_mirror_p"])
    perm = np.random.default_rng(np.random.SeedSequence([seed, 0])
                                 ).permutation(config["vertices"])
    out = edges.copy()
    out[:, 0] = perm[edges[:, 0]]
    out[:, 2] = perm[edges[:, 2]]
    return np.unique(out, axis=0)


def out_csr(num_vertices: int, edges: np.ndarray):
    """``(indptr, label, dst)`` of the out-edges, by source."""
    order = np.argsort(edges[:, 0], kind="stable")
    e = edges[order]
    indptr = np.zeros(num_vertices + 1, np.int64)
    np.add.at(indptr, e[:, 0] + 1, 1)
    return np.cumsum(indptr), e[:, 1], e[:, 2]
