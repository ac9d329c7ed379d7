"""Pallas TPU kernel: batched RLC query join (Algorithm 1 on device).

One grid step evaluates one query ``(s, t, mr)``. The ``L_out(s)`` and
``L_in(t)`` rows are streamed into VMEM by scalar-prefetch indexed
BlockSpecs (the TPU answer to the pointer-chase gather); Case 2 is a pair
of vector compares and Case 1 an ``(E, E)`` broadcast join on the VPU —
the dense equivalent of the paper's aid-ordered merge join.

TPU tiling: a block's last two dims must be multiples of ``(8, 128)`` or
span the whole array. So each step fetches the aligned group of ``rb``
(≤ 8) rows that holds the wanted row, and picks the row inside VMEM with
a dynamic sublane slice; ``E`` rides whole. The join compares in int32
(masked slots get sentinels that never match), since Mosaic cannot
reshape a bool vector into a column. The answer lands in a ``(1, 128)``
lane row per query.

Inputs are the padded DeviceIndex arrays (PAD = -1 never matches).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.constants import PAD

_LANES = 128


def _mergejoin_kernel(s_ref, t_ref, mr_ref,           # scalar prefetch
                      oh_ref, om_ref, ih_ref, im_ref,  # (rb, E) row groups
                      o_ref,                           # (1, 128) int32 out
                      *, rb: int, row_base_out: int, row_base_in: int):
    q = pl.program_id(0)
    s = s_ref[q]
    t = t_ref[q]
    mr = mr_ref[q]
    so = pl.ds((s - row_base_out) % rb, 1)
    ti = pl.ds((t - row_base_in) % rb, 1)
    oh = oh_ref[so, :]                                 # (1, E)
    om = om_ref[so, :]
    ih = ih_ref[ti, :]
    im = im_ref[ti, :]
    case2 = jnp.where(((oh == t) & (om == mr)) | ((ih == s) & (im == mr)),
                      1, 0)
    o_key = jnp.where((om == mr) & (oh != PAD), oh, PAD - 1)
    i_key = jnp.where((im == mr) & (ih != PAD), ih, PAD - 2)
    case1 = jnp.where(o_key.reshape(-1, 1) == i_key, 1, 0)   # (E, E)
    ans = jnp.maximum(jnp.max(case2), jnp.max(case1))
    o_ref[...] = jnp.full(o_ref.shape, ans, jnp.int32)


def query_batch(out_hub: jax.Array, out_mr: jax.Array, in_hub: jax.Array,
                in_mr: jax.Array, s: jax.Array, t: jax.Array,
                mr: jax.Array, *, interpret: bool = False,
                row_base_out: int = 0, row_base_in: int = 0) -> jax.Array:
    """Returns (Q,) bool answers. E (row length) rides fully in VMEM.

    ``row_base_*`` offset the scalar-prefetch row lookups for
    row-windowed shard layouts (storage row = vertex id - base); the
    kernel body still compares the global ids in ``s``/``t``.
    """
    n, E = out_hub.shape
    Q = s.shape[0]
    rb = min(8, n)
    out_rows = lambda q, s_r, t_r, m_r: ((s_r[q] - row_base_out) // rb, 0)  # noqa: E731
    in_rows = lambda q, s_r, t_r, m_r: ((t_r[q] - row_base_in) // rb, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(Q,),
        in_specs=[pl.BlockSpec((rb, E), out_rows),
                  pl.BlockSpec((rb, E), out_rows),
                  pl.BlockSpec((rb, E), in_rows),
                  pl.BlockSpec((rb, E), in_rows)],
        out_specs=pl.BlockSpec((None, 1, _LANES),
                               lambda q, s_r, t_r, m_r: (q, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_mergejoin_kernel, rb=rb,
                          row_base_out=row_base_out,
                          row_base_in=row_base_in),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, 1, _LANES), jnp.int32),
        interpret=interpret,
        name="rlc_mergejoin",
    )(s.astype(jnp.int32), t.astype(jnp.int32), mr.astype(jnp.int32),
      out_hub, out_mr, in_hub, in_mr)
    return out[:, 0, 0] > 0
