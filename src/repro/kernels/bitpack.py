"""Pallas TPU kernel: bit-packed OR-AND matmul (beyond-paper optimization).

Rationale: once the reachability frontier saturates, the semiring matmul
is *memory-bound* — its operands are 0/1 values occupying a full f32 lane
each. Packing the N dimension 32-to-a-uint32 cuts HBM traffic of the right
operand and the output by 32x, trading MXU dots for VPU ``where``+``or``
ops. Profitable exactly when the memory roofline term dominates.

``out_packed[m, w] = OR_k a[m, k] ? b_packed[k, w] : 0``   (bitwise OR)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def pack_bits(x: jax.Array) -> jax.Array:
    """(..., N) 0/1 -> (..., N//32) uint32 (bit j of word w = col 32w+j)."""
    n = x.shape[-1]
    assert n % 32 == 0, n
    xb = (x > 0).astype(jnp.uint32).reshape(*x.shape[:-1], n // 32, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return (xb << shifts).sum(axis=-1, dtype=jnp.uint32)


def unpack_bits(xp: jax.Array, dtype=jnp.float32) -> jax.Array:
    w = xp.shape[-1]
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (xp[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*xp.shape[:-1], w * 32).astype(dtype)


def _bitpack_kernel(a_ref, bp_ref, o_ref, acc_ref, *, k_steps: int,
                    bk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]          # (bm, bk) f32 0/1
    bp = bp_ref[...]        # (bk, bw) uint32

    def body(kk, acc):
        mask = jax.lax.dynamic_slice_in_dim(a, kk, 1, axis=1) > 0  # (bm, 1)
        word = jax.lax.dynamic_slice_in_dim(bp, kk, 1, axis=0)     # (1, bw)
        return acc | jnp.where(mask, word, jnp.uint32(0))

    acc_ref[...] = jax.lax.fori_loop(0, bk, body, acc_ref[...])

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _finish():
        o_ref[...] = acc_ref[...]


def bitpack_matmul(a: jax.Array, b_packed: jax.Array, *, bm: int = 128,
                   bk: int = 128, bw: int = 128,
                   interpret: bool = False) -> jax.Array:
    """OR-AND product with bit-packed right operand / output.

    a: (M, K) f32 0/1;  b_packed: (K, W) uint32;  out: (M, W) uint32.
    """
    m, k = a.shape
    k2, w = b_packed.shape
    assert k == k2
    bm, bk, bw = min(bm, m), min(bk, k), min(bw, w)
    assert m % bm == 0 and k % bk == 0 and w % bw == 0
    grid = (m // bm, w // bw, k // bk)
    return pl.pallas_call(
        functools.partial(_bitpack_kernel, k_steps=grid[2], bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bw), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bw), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, w), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((bm, bw), jnp.uint32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a, b_packed)
