"""TPU-native dense boolean-semiring engine.

The paper's kernel-BFS guided by ``L^+`` is a BFS over the product automaton
``V x {0..m-1}``; one step is a boolean mat-vec with the label-sliced
adjacency. Batching all sources turns the whole index computation into
boolean *matrix-matrix* products — MXU work. This module provides:

* ``mr_step_matrix``   — ``M_L = A[l1] (x) ... (x) A[lm]`` (OR-AND chain);
* ``plus_closure``     — ``M^+`` by log-doubling (``h <= |V|`` repeats);
* ``DenseEngine``      — ETC-equivalent all-pairs ``S^k`` oracle on device;
* ``build_condensed_device`` — hub-batched pruned 2-hop labeling: the
  paper's Algorithm 2 re-derived as masked matmuls. PR2 is the aid mask;
  PR1 is a vectorized coverage query (one boolean matmul per hub batch);
  batch size 1 reproduces the sequential pruning schedule, larger batches
  trade a few redundant entries for data-parallel throughput (soundness +
  completeness preserved — the PLL-style argument).

Boolean values ride in float32/bf16 (MXU dtype); OR == saturating add via
``dot > 0``. The inner product is swappable for the Pallas kernel in
:mod:`repro.kernels.bool_semiring`.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import LabeledGraph
from .minimum_repeat import LabelSeq, enumerate_mrs, mr_id_space
from .rlc_index import RLCIndex

MatMul = Callable[[jax.Array, jax.Array], jax.Array]


def bool_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """OR-AND semiring product for 0/1 float arrays (reference path)."""
    return (jnp.matmul(a, b, preferred_element_type=jnp.float32) > 0
            ).astype(a.dtype)


def mr_step_matrix(A: jax.Array, mr: Sequence[int],
                   matmul: MatMul = bool_matmul) -> jax.Array:
    """``M_L[u, v] = 1`` iff a path u->v spells exactly ``L``. ``A`` is the
    (|L|, n, n) label-sliced adjacency stack."""
    M = A[mr[0]]
    for lab in mr[1:]:
        M = matmul(M, A[lab])
    return M


def plus_closure(M: jax.Array, n_iters: Optional[int] = None,
                 matmul: MatMul = bool_matmul) -> jax.Array:
    """``M^+ = M | M^2 | ...`` via log-doubling: R_{i+1} = R_i | R_i R_i
    covers powers 1..2^(i+1); minimal repeat count is <= |V|."""
    n = M.shape[-1]
    iters = n_iters if n_iters is not None else max(1, math.ceil(
        math.log2(max(n, 2))))
    R = M
    for _ in range(iters):
        R = jnp.maximum(R, matmul(R, R))
    return R


@partial(jax.jit, static_argnames=("mrs", "matmul"))
def _all_mr_reach(A: jax.Array, mrs: Tuple[LabelSeq, ...],
                  matmul: MatMul = bool_matmul) -> jax.Array:
    """Stack of ``R_L`` for every MR (C, n, n). MRs grouped by length so
    the per-length chains share compiled code."""
    outs = []
    for mr in mrs:
        outs.append(plus_closure(mr_step_matrix(A, mr, matmul),
                                 matmul=matmul))
    return jnp.stack(outs)


@dataclass
class DenseEngine:
    """All-pairs ``S^k`` on device — the TPU analog of the paper's ETC."""

    graph: LabeledGraph
    k: int
    mrs: Tuple[LabelSeq, ...]
    mr_ids: Dict[LabelSeq, int]
    reach: np.ndarray  # (C, n, n) bool — reach[c, u, v] = u ~~mr_c^+~~> v

    @staticmethod
    def build(graph: LabeledGraph, k: int,
              matmul: MatMul = bool_matmul) -> "DenseEngine":
        mrs = enumerate_mrs(graph.num_labels, k)
        A = jnp.asarray(graph.label_adjacency(np.float32))
        R = _all_mr_reach(A, mrs, matmul)
        return DenseEngine(graph, k, mrs, mr_id_space(graph.num_labels, k),
                           np.asarray(R) > 0)

    def query(self, s: int, t: int, L: Sequence[int]) -> bool:
        c = self.mr_ids.get(tuple(L))
        if c is None:
            return False
        return bool(self.reach[c, s, t])

    def s_k(self, u: int, v: int) -> set:
        return {self.mrs[c] for c in range(len(self.mrs))
                if self.reach[c, u, v]}

    def num_true_pairs(self) -> int:
        return int(self.reach.sum())


# ------------------------------------------------------------------ #
# Hub-batched condensed 2-hop build (device Algorithm 2)
# ------------------------------------------------------------------ #
@partial(jax.jit, donate_argnums=(0, 1))
def _hub_batch_step(OUT: jax.Array, IN: jax.Array, R: jax.Array,
                    aid: jax.Array, hubs: jax.Array) -> Tuple[jax.Array,
                                                              jax.Array]:
    """Add entries for one batch of hubs with PR1/PR2 masks.

    OUT[c, y, x] = 1 iff (x, mr_c) in L_out(y);  IN[c, y, x] similarly.
    For hub h (column/row slices of R):
      backward (L_out additions at every y reaching h):
        cand = R[c, :, h] & aid(h) <= aid(y) & ~Query(y, h, mr_c)
      forward (L_in additions at every y reached from h): symmetric.
    Query(s, t, c) = OUT[c,s,t] | IN[c,t,s] | OR_x OUT[c,s,x] & IN[c,t,x].
    """
    dtypef = OUT.dtype
    aid_h = aid[hubs]                                    # (B,)
    pr2 = (aid_h[None, :] <= aid[:, None]).astype(dtypef)  # (n, B) keep-mask

    # ---- backward: entries (h, c) at L_out(y) ----
    reach_to_h = R[:, :, hubs]                           # (C, n, B)
    IN_h = IN[:, hubs, :]                                # (C, B, n)
    # Case-1 coverage: OR_x OUT[c,y,x] & IN[c,h,x]
    cov1 = (jnp.einsum("cyx,cbx->cyb", OUT, IN_h,
                       preferred_element_type=jnp.float32) > 0)
    cov2 = OUT[:, :, hubs] > 0                           # direct (h,c) there
    cov3 = jnp.swapaxes(IN_h, 1, 2)[:, :, :] > 0         # (y, c) in L_in(h)?
    # cov3[c, y, b] = IN[c, h_b, y]: (y, mr) in L_in(h) — Case 2 mirror.
    covered = cov1 | cov2 | cov3
    cand_out = reach_to_h * pr2[None] * (1.0 - covered.astype(dtypef))
    OUT = OUT.at[:, :, hubs].max(cand_out)

    # ---- forward: entries (h, c) at L_in(y) ----
    reach_from_h = jnp.swapaxes(R[:, hubs, :], 1, 2)     # (C, n, B)
    OUT_h = OUT[:, hubs, :]                              # (C, B, n) updated!
    cov1f = (jnp.einsum("cyx,cbx->cyb", IN, OUT_h,
                        preferred_element_type=jnp.float32) > 0)
    cov2f = IN[:, :, hubs] > 0
    cov3f = jnp.swapaxes(OUT_h, 1, 2) > 0                # (t, c) in L_out(h)
    coveredf = cov1f | cov2f | cov3f
    cand_in = reach_from_h * pr2[None] * (1.0 - coveredf.astype(dtypef))
    IN = IN.at[:, :, hubs].max(cand_in)
    return OUT, IN


def build_condensed_device(graph: LabeledGraph, k: int,
                           hub_batch: int = 1,
                           matmul: MatMul = bool_matmul,
                           reach: Optional[np.ndarray] = None
                           ) -> Tuple[RLCIndex, DenseEngine]:
    """Device-side condensed RLC index build (see module docstring)."""
    eng = (DenseEngine(graph, k, enumerate_mrs(graph.num_labels, k),
                       mr_id_space(graph.num_labels, k), reach)
           if reach is not None else DenseEngine.build(graph, k, matmul))
    n, C = graph.num_vertices, len(eng.mrs)
    aid = graph.access_ids()
    order = graph.access_order()
    R = jnp.asarray(eng.reach.astype(np.float32))
    OUT = jnp.zeros((C, n, n), jnp.float32)
    IN = jnp.zeros((C, n, n), jnp.float32)
    aid_j = jnp.asarray(aid, jnp.int32)
    for i in range(0, n, hub_batch):
        hubs = jnp.asarray(order[i:i + hub_batch], jnp.int32)
        OUT, IN = _hub_batch_step(OUT, IN, R, aid_j, hubs)
    OUT_np = np.asarray(OUT) > 0
    IN_np = np.asarray(IN) > 0
    idx = RLCIndex(n, k, aid)
    cs, ys, xs = np.nonzero(OUT_np)
    for c, y, x in zip(cs.tolist(), ys.tolist(), xs.tolist()):
        idx.add_out(y, x, eng.mrs[c])
    cs, ys, xs = np.nonzero(IN_np)
    for c, y, x in zip(cs.tolist(), ys.tolist(), xs.tolist()):
        idx.add_in(y, x, eng.mrs[c])
    return idx, eng
