"""JAX/Pallas build backend: hub waves through ``frontier_step_many``.

The wave contract is the same as the numpy engine's — expand a batch of
``(row, vertex)`` frontier pairs one label step — but the expansion runs
as an OR-AND matmul against the dense label-sliced adjacency stack on
the accelerator, batching every kernel/phase row of a hub's product
automaton through one :func:`repro.kernels.label_frontier.
frontier_step_many` call. Frontier hand-off between device and the
host-side pruned-insert loop travels bit-packed through
:mod:`repro.kernels.bitpack` (32 vertices per word — 32x less transfer
than the f32 frontier it replaces).

Hub batching deliberately stops at one hub: PR1 reads the entries every
earlier hub completed, so cross-hub waves cannot stay bit-identical
(see :mod:`repro.build.batched`). The backend defaults to hybrid
dispatch (device waves for the widest hubs only); ``mode="vector"``
sends every hub through the kernel path, as the equivalence tests do.
When JAX runs on the CPU the kernels run in the Pallas interpreter —
correct, and slow. Wave row counts are padded to a power of two so the
device compiles a bounded set of shapes.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from repro.core.graph import LabeledGraph

from .base import register_backend
from .batched import BatchedBackend, FrontierEngine

_EMPTY = np.empty(0, dtype=np.int64)


def _pad128(n: int) -> int:
    return max(128, -(-n // 128) * 128)


class PallasEngine(FrontierEngine):
    def __init__(self, graph: LabeledGraph, interpret: Optional[bool] = None):
        import jax.numpy as jnp  # deferred: importing repro.build stays jax-free
        from repro.device import on_cpu

        self.V = graph.num_vertices
        self.nl = graph.num_labels
        self.Vp = _pad128(self.V)
        self.interpret = on_cpu() if interpret is None else interpret
        self.waves = 0              # device waves run
        self.shapes = set()         # distinct (rows, Vp) wave shapes
        A = np.zeros((self.nl, self.Vp, self.Vp), dtype=np.float32)
        e = graph.edges
        A[e[:, 1], e[:, 0], e[:, 2]] = 1
        self._A = (jnp.asarray(A),                      # forward: u -> v
                   jnp.asarray(np.swapaxes(A, 1, 2)))  # backward: v -> u

    # ------------------------------------------------------------------ #
    def _step(self, F: np.ndarray, labels: np.ndarray, backward: bool
              ) -> np.ndarray:
        """One device wave: returns the (R, V) boolean next frontier.
        The device result round-trips bit-packed (kernels/bitpack)."""
        from repro.kernels.ops import frontier_wave_packed

        R = len(F)
        Rp = 1 << (R - 1).bit_length()                # pow2 shape set
        Fp = np.zeros((Rp, self.Vp), np.float32)
        Fp[:R] = F
        lab = np.zeros(Rp, np.int32)
        lab[:R] = labels
        self.waves += 1
        self.shapes.add(Fp.shape)
        packed = np.asarray(frontier_wave_packed(
            Fp, self._A[backward], lab, interpret=self.interpret))[:R]
        bits = (packed[..., None] >> np.arange(32, dtype=np.uint32)) & 1
        return bits.reshape(R, self.Vp)[:, :self.V].astype(bool)

    def expand(self, rows: np.ndarray, ys: np.ndarray, rowlab: np.ndarray,
               dstrow: np.ndarray, backward: bool
               ) -> Tuple[np.ndarray, np.ndarray]:
        R = len(rowlab)
        F = np.zeros((R, self.Vp), dtype=np.float32)
        F[rows, ys] = 1.0
        dense = self._step(F, rowlab, backward)
        nr, ny = np.nonzero(dense)
        if not nr.size:
            return _EMPTY, _EMPTY
        return dstrow[nr], ny.astype(np.int64)

    def expand_fanout(self, rows: np.ndarray, ys: np.ndarray,
                      backward: bool) -> Tuple[np.ndarray, np.ndarray]:
        # duplicate each active parent row once per label; the multi-label
        # kernel then expands all (parent, label) fans in one call
        parents = np.unique(rows)
        P, nl = len(parents), self.nl
        F = np.zeros((P * nl, self.Vp), dtype=np.float32)
        loc = np.searchsorted(parents, rows)
        for l in range(nl):
            F[loc * nl + l, ys] = 1.0
        labels = np.tile(np.arange(nl, dtype=np.int32), P)
        dense = self._step(F, labels, backward)
        nr, ny = np.nonzero(dense)
        if not nr.size:
            return _EMPTY, _EMPTY
        child = parents[nr // nl] * nl + (nr % nl)
        return child, ny.astype(np.int64)


class PallasBackend(BatchedBackend):
    """Hybrid build whose wide-hub waves run on the Pallas kernels."""

    name = "pallas"

    def __init__(self, *args, interpret: Optional[bool] = None, **kw):
        super().__init__(*args, **kw)
        self.interpret = interpret
        #: the last build's engine (its wave counters say how much of
        #: the build ran on the device)
        self.engine: Optional[PallasEngine] = None

    def _make_engine(self, graph: LabeledGraph) -> FrontierEngine:
        self.engine = PallasEngine(graph, interpret=self.interpret)
        return self.engine


register_backend("pallas", PallasBackend)
