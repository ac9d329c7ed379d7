"""Batched index-construction engine (Algorithm 2 as a staged pipeline).

Public surface::

    from repro.build import build_rlc_index, build_rlc_index_with_stats
    idx = build_rlc_index(g, k=2)                       # auto -> numpy
    idx, st = build_rlc_index_with_stats(g, 2, backend="pallas")
    get_backend("numpy", mode="vector").build(g, 2)     # explicit control

Backends (see ``README.md`` in this package for the design):

============  ==========================================================
``python``    faithful sequential Algorithm 2 — the reference oracle
``numpy``     hybrid scalar / vectorized bitset waves on label CSR
``pallas``    hybrid with waves batched through the TPU ``frontier_step``
              kernels (interpreted when JAX is on the CPU; request
              explicitly)
``parallel``  hub-partitioned epoch/merge workers over a list-scheduled
              phase DAG (``workers=N``; each worker runs the numpy
              hybrid on a hub-sliced mirror)
============  ==========================================================

All backends produce bit-identical index entries and pruning counters.
"""
from __future__ import annotations

from typing import Tuple

from repro.core.graph import LabeledGraph
from repro.core.rlc_index import RLCIndex

from .base import (AUTO_ORDER, BuildBackend, BuildStats, PrunedInserter,
                   access_schedule, get_backend, list_backends,
                   register_backend)
from .reference import IndexBuilder, PythonBackend
from .numpy_backend import NumpyBackend

# imports jax only when a pallas build starts
from .pallas_backend import PallasBackend

# multi-worker epoch/merge construction over the phase DAG
from .parallel import ParallelBackend

# the incremental engine rides on the registered batched backends
from .delta import DeltaBuilder, DeltaResult, GraphDelta

__all__ = [
    "AUTO_ORDER", "BuildBackend", "BuildStats", "DeltaBuilder",
    "DeltaResult", "GraphDelta", "IndexBuilder", "NumpyBackend",
    "PallasBackend", "ParallelBackend", "PrunedInserter", "PythonBackend",
    "access_schedule", "build_rlc_index", "build_rlc_index_with_stats",
    "get_backend", "list_backends", "register_backend",
]


def build_rlc_index_with_stats(graph: LabeledGraph, k: int,
                               backend: str = "auto", observer=None, **kw
                               ) -> Tuple[RLCIndex, BuildStats]:
    """Build the RLC index with the chosen backend; returns (index, stats).

    ``**kw`` reaches the backend constructor (``use_pr1/2/3`` everywhere;
    ``mode``/``scalar_threshold`` on the batched backends; ``interpret``
    on pallas). ``observer``: optional
    :class:`repro.obs.BuildPhaseObserver` receiving per-(hub, direction)
    phase timings and counter deltas.
    """
    return get_backend(backend, **kw).set_observer(observer).build(graph, k)


def build_rlc_index(graph: LabeledGraph, k: int, backend: str = "auto",
                    **kw) -> RLCIndex:
    return build_rlc_index_with_stats(graph, k, backend=backend, **kw)[0]
