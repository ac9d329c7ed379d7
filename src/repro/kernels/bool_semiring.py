"""Pallas TPU kernel: blocked OR-AND (boolean semiring) matmul.

The compute hot-spot of the TPU-native RLC engine (DESIGN §3): reachability
closures and MR step-matrix chains are chains of these products. 0/1 values
ride in f32/bf16 so the MXU does the AND-accumulate as a dot; OR is the
``> 0`` threshold applied once per output tile on the f32 accumulator.

Grid: ``(M/bm, N/bn, K/bk)`` with the K loop innermost; one VMEM f32
accumulator tile per (i, j). Block defaults are MXU-aligned (128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bool_mm_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] > 0).astype(o_ref.dtype)


def _fused_closure_kernel(a_ref, b_ref, rij_ref, o_ref, acc_ref, *,
                          k_steps: int):
    """One fused doubling step: O = R | R @ R. The (i, j) tile of R rides
    in as a third operand so the OR happens in VMEM (saves one HBM
    round-trip of the output tile vs. matmul-then-max)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _finish():
        o_ref[...] = jnp.maximum((acc_ref[...] > 0).astype(o_ref.dtype),
                                 rij_ref[...])


def bool_matmul(a: jax.Array, b: jax.Array, *, bm: int = 128,
                bk: int = 128, bn: int = 128,
                interpret: bool = False) -> jax.Array:
    """``(a @ b) > 0`` over the OR-AND semiring. Shapes must tile evenly
    (use :mod:`repro.kernels.ops` for padded dispatch)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_bool_mm_kernel, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a, b)


def closure_step(r: jax.Array, *, bm: int = 128, bk: int = 128,
                 bn: int = 128, interpret: bool = False) -> jax.Array:
    """Fused ``R | R @ R`` (log-doubling step). ``r`` must be square and
    tile evenly."""
    n = r.shape[0]
    assert r.shape == (n, n)
    bm, bk, bn = min(bm, n), min(bk, n), min(bn, n)
    assert n % bm == 0 and n % bk == 0 and n % bn == 0
    grid = (n // bm, n // bn, n // bk)
    return pl.pallas_call(
        functools.partial(_fused_closure_kernel, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), r.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(r, r, r)
