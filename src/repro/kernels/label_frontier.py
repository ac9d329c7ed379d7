"""Pallas TPU kernels: label-guided product-automaton frontier steps.

One kernel step of the (batched) kernel-BFS: given the frontier matrix
``F`` (sources x vertices) at automaton position ``p`` and the stacked
per-label adjacency ``A`` (|L|, V, V), compute ``F @ A[label]`` over the
OR-AND semiring. The *label* selects the adjacency slice via a
scalar-prefetch indexed BlockSpec — the whole guided BFS runs without
materializing the selected slice in HBM.

Three granularities:

* :func:`frontier_step`       — one shared label for the whole batch;
* :func:`frontier_step_many`  — one label *per frontier row* (the
  batched index builder drives every kernel/phase of a hub's product
  automaton through a single call);
* :func:`frontier_steps`      — multi-step: a ``(T, R)`` label schedule
  scanned on device with a per-step row permutation (the phase shift of
  the product automaton), for advancing several waves without a host
  round-trip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _frontier_kernel(lab_ref, f_ref, a_ref, o_ref, acc_ref, *,
                     k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(f_ref[...], a_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] > 0).astype(o_ref.dtype)


def frontier_step(frontier: jax.Array, A: jax.Array, label: jax.Array, *,
                  bb: int = 128, bk: int = 128, bn: int = 128,
                  interpret: bool = False) -> jax.Array:
    """next[b, v] = OR_u frontier[b, u] & A[label, u, v].

    frontier: (B, V) f32 0/1;  A: (|L|, V, V) f32;  label: () int32.
    """
    B, V = frontier.shape
    nl, V1, V2 = A.shape
    assert V == V1 == V2
    bb, bk, bn = min(bb, B), min(bk, V), min(bn, V)
    assert B % bb == 0 and V % bk == 0 and V % bn == 0
    grid = (B // bb, V // bn, V // bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bk), lambda i, j, kk, lab: (i, kk)),
            pl.BlockSpec((None, bk, bn),
                         lambda i, j, kk, lab: (lab[0], kk, j)),
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j, kk, lab: (i, j)),
        scratch_shapes=[pltpu.VMEM((bb, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_frontier_kernel, k_steps=grid[2]),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, V), frontier.dtype),
        interpret=interpret,
        name="rlc_frontier_step",
    )(label.reshape(1).astype(jnp.int32), frontier, A)


def frontier_step_many(frontier: jax.Array, A: jax.Array,
                       labels: jax.Array, *, bk: int = 128, bn: int = 128,
                       interpret: bool = False) -> jax.Array:
    """next[r, v] = OR_u frontier[r, u] & A[labels[r], u, v].

    Per-row labels: row ``r`` of the frontier advances along its own
    adjacency slice, selected by the scalar-prefetched ``labels`` vector
    in the BlockSpec index map — many kernels / automaton phases of
    Algorithm 2's kernel-BFS batch through one call.

    frontier: (R, V) f32 0/1;  A: (|L|, V, V) f32;  labels: (R,) int32.
    """
    R, V = frontier.shape
    nl, V1, V2 = A.shape
    assert V == V1 == V2 and labels.shape == (R,)
    bk, bn = min(bk, V), min(bn, V)
    assert V % bk == 0 and V % bn == 0
    grid = (R, V // bn, V // bk)
    # one frontier row per grid step: rows ride as (R, 1, V) so each
    # block's last two dims are (1, 128·m), which the TPU tiling rule
    # accepts (a (1, bk) block of an (R, V) array it refuses)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 1, bk), lambda i, j, kk, lab: (i, 0, kk)),
            pl.BlockSpec((None, bk, bn),
                         lambda i, j, kk, lab: (lab[i], kk, j)),
        ],
        out_specs=pl.BlockSpec((None, 1, bn),
                               lambda i, j, kk, lab: (i, 0, j)),
        scratch_shapes=[pltpu.VMEM((1, bn), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_frontier_kernel, k_steps=grid[2]),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, 1, V), frontier.dtype),
        interpret=interpret,
        name="rlc_frontier_step_many",
    )(labels.astype(jnp.int32), frontier.reshape(R, 1, V), A)
    return out.reshape(R, V)


def frontier_steps(frontier: jax.Array, A: jax.Array, labels: jax.Array,
                   dst: jax.Array, *, bk: int = 128, bn: int = 128,
                   interpret: bool = False) -> jax.Array:
    """``T`` chained :func:`frontier_step_many` waves on device.

    After wave ``t``, row ``r``'s expansion lands in row ``dst[t, r]``
    (the product automaton's phase shift; each ``dst[t]`` must be a
    permutation). No visited-set pruning happens between waves — callers
    interleave host-side pruning only at repeat boundaries and use this
    to advance the off-boundary phases in one shot.

    frontier: (R, V);  labels: (T, R) int32;  dst: (T, R) int32.
    """
    T, R = labels.shape
    assert dst.shape == (T, R) and frontier.shape[0] == R

    def body(F, step):
        labs, d = step
        G = frontier_step_many(F, A, labs, bk=bk, bn=bn,
                               interpret=interpret)
        return jnp.zeros_like(G).at[d].set(G), None

    out, _ = jax.lax.scan(body, frontier,
                          (labels.astype(jnp.int32),
                           dst.astype(jnp.int32)))
    return out
