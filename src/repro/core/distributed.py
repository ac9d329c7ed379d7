"""Distributed RLC index build + query serving on a device mesh (DESIGN §3/§5).

Layout
------
* adjacency / reachability matrices: rows (source vertices) sharded over the
  ``data`` mesh axis; columns replicated (or sharded over ``model`` for the
  widest graphs).
* semiring matmuls: row-parallel ``shard_map`` — each shard holds a row
  block of the left operand, all-gathers the right operand once per step
  (ring all-gather on the ICI), and emits its row block of the product.
  This is the *manual-collective* path; a GSPMD path (`jit` +
  ``with_sharding_constraint``) is provided for comparison and used by the
  dry-run lowering.
* queries: embarrassingly parallel — sharded over ``("pod", "data")``; the
  frozen index is replicated per pod (paper's serving story).

Fault tolerance: the hub-batched build checkpoints ``(OUT, IN, next_hub)``
between batches (see :mod:`repro.ft.elastic`), so a failed build resumes
from the last completed batch, and a shrunk mesh re-shards the same arrays.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .dense import DenseEngine, build_condensed_device
from .graph import LabeledGraph
from .minimum_repeat import enumerate_mrs
from .rlc_index import RLCIndex

def make_rlc_mesh(data: Optional[int] = None, pod: int = 1) -> Mesh:
    """1-pod mesh over available devices: axes ("pod", "data")."""
    nd = len(jax.devices())
    data = data or (nd // pod)
    devs = np.asarray(jax.devices()[:pod * data]).reshape(pod, data)
    return Mesh(devs, ("pod", "data"))


# ------------------------------------------------------------------ #
# Row-parallel semiring matmul (manual collectives)
# ------------------------------------------------------------------ #
def shmap_bool_matmul(mesh: Mesh, axis: str = "data"):
    """Returns an OR-AND matmul: left rows sharded over ``axis``; right
    operand all-gathered (tiled ring) inside the shard."""

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(axis, None), P(axis, None)),
             out_specs=P(axis, None))
    def matmul(a_blk, b_blk):
        b_full = jax.lax.all_gather(b_blk, axis, axis=0, tiled=True)
        acc = jnp.matmul(a_blk, b_full,
                         preferred_element_type=jnp.float32)
        return (acc > 0).astype(a_blk.dtype)

    return matmul


def distributed_plus_closure(M: jax.Array, mesh: Mesh,
                             axis: str = "data") -> jax.Array:
    """Log-doubling closure with the row-parallel semiring matmul."""
    mm = shmap_bool_matmul(mesh, axis)
    n = M.shape[-1]
    R = M
    for _ in range(max(1, math.ceil(math.log2(max(n, 2))))):
        R = jnp.maximum(R, mm(R, R))
    return R


def distributed_all_mr_reach(graph: LabeledGraph, k: int, mesh: Mesh,
                             axis: str = "data") -> np.ndarray:
    """(C, n, n) R_L stack computed with row-sharded semiring matmuls.
    Rows are padded to a multiple of the axis size."""
    mrs = enumerate_mrs(graph.num_labels, k)
    n = graph.num_vertices
    p = mesh.shape[axis]
    n_pad = ((n + p - 1) // p) * p
    A_np = np.zeros((graph.num_labels, n_pad, n_pad), np.float32)
    A_np[:, :n, :n] = graph.label_adjacency(np.float32)
    shard = NamedSharding(mesh, P(None, axis, None))
    A = jax.device_put(jnp.asarray(A_np), shard)
    mm = shmap_bool_matmul(mesh, axis)
    outs = []
    for mr in mrs:
        M = A[mr[0]]
        for lab in mr[1:]:
            M = mm(M, A[lab])
        outs.append(distributed_plus_closure(M, mesh, axis))
    R = np.asarray(jnp.stack(outs))[:, :n, :n]
    return R > 0


def distributed_build(graph: LabeledGraph, k: int, mesh: Mesh,
                      hub_batch: int = 8) -> Tuple[RLCIndex, DenseEngine]:
    """Distributed condensed build: R_L on the mesh, then the hub-batched
    pruned labeling (dense.py) with row-sharded coverage matmuls."""
    R = distributed_all_mr_reach(graph, k, mesh)
    return build_condensed_device(graph, k, hub_batch=hub_batch, reach=R)


# ------------------------------------------------------------------ #
# Distributed query serving
# ------------------------------------------------------------------ #
def distributed_query_batch(dev_index, s: np.ndarray, t: np.ndarray,
                            mr: np.ndarray, mesh: Mesh) -> np.ndarray:
    """Shard the query batch over every mesh axis; index replicated.
    Pads the batch up to a multiple of the mesh size."""
    from .device_index import _query_batch_ref

    axes = tuple(mesh.axis_names)
    nshard = math.prod(mesh.shape[a] for a in axes)
    Q = len(s)
    Qp = ((Q + nshard - 1) // nshard) * nshard
    pad = Qp - Q

    def pad1(x):
        return np.concatenate([x, np.zeros(pad, x.dtype)]) if pad else x

    qshard = NamedSharding(mesh, P(axes))
    rep = NamedSharding(mesh, P())
    args = [jax.device_put(jnp.asarray(x), rep)
            for x in (dev_index.out_hub, dev_index.out_mr,
                      dev_index.in_hub, dev_index.in_mr)]
    qargs = [jax.device_put(jnp.asarray(pad1(np.asarray(x, np.int32))),
                            qshard) for x in (s, t, mr)]
    fn = jax.jit(_query_batch_ref,
                 in_shardings=(rep,) * 4 + (qshard,) * 3,
                 out_shardings=qshard)
    out = np.asarray(fn(*args, *qargs))
    return out[:Q]
