"""Jit'd public wrappers for the Pallas kernels: padding to block multiples,
interpret-mode dispatch when JAX runs on the CPU (kernels compile for the
TPU; on the host they run in the Pallas interpreter, which the CPU tests
use), and a uniform ``matmul``-shaped interface the dense engine can plug
in. ``interpret=None`` asks :func:`repro.device.on_cpu` at trace time.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.device import on_cpu

from . import bitpack as _bitpack
from . import bool_semiring as _bs
from . import label_frontier as _lf
from . import mergejoin as _mj


def _interpret(interpret: Optional[bool]) -> bool:
    return on_cpu() if interpret is None else interpret


def _pad_to(x: jax.Array, mults):
    pads = []
    needs = False
    for dim, mult in zip(x.shape, mults):
        target = ((dim + mult - 1) // mult) * mult
        pads.append((0, target - dim))
        needs |= target != dim
    return jnp.pad(x, pads) if needs else x


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def bool_matmul(a: jax.Array, b: jax.Array, bm: int = 128, bk: int = 128,
                bn: int = 128, interpret: Optional[bool] = None
                ) -> jax.Array:
    """Padded OR-AND semiring matmul via the Pallas kernel."""
    interpret = _interpret(interpret)
    m, k = a.shape
    _, n = b.shape
    bm_, bk_, bn_ = min(bm, m), min(bk, k), min(bn, n)
    ap = _pad_to(a, (bm_, bk_))
    bp = _pad_to(b, (bk_, bn_))
    out = _bs.bool_matmul(ap, bp, bm=bm_, bk=bk_, bn=bn_,
                          interpret=interpret)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def closure_step(r: jax.Array, bm: int = 128, bk: int = 128, bn: int = 128,
                 interpret: Optional[bool] = None) -> jax.Array:
    interpret = _interpret(interpret)
    n = r.shape[0]
    b = min(bm, n)
    rp = _pad_to(r, (b, b))
    out = _bs.closure_step(rp, bm=min(bm, rp.shape[0]),
                           bk=min(bk, rp.shape[0]),
                           bn=min(bn, rp.shape[0]), interpret=interpret)
    return out[:n, :n]


@functools.partial(jax.jit, static_argnames=("interpret", "row_base_out",
                                             "row_base_in"))
def mergejoin_query(out_hub, out_mr, in_hub, in_mr, s, t, mr,
                    interpret: Optional[bool] = None,
                    row_base_out: int = 0,
                    row_base_in: int = 0) -> jax.Array:
    interpret = _interpret(interpret)
    return _mj.query_batch(out_hub, out_mr, in_hub, in_mr, s, t, mr,
                           interpret=interpret, row_base_out=row_base_out,
                           row_base_in=row_base_in)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitpack_matmul(a, b_packed, interpret: Optional[bool] = None):
    interpret = _interpret(interpret)
    m, k = a.shape
    _, w = b_packed.shape
    bm, bk, bw = min(128, m), min(128, k), min(128, w)
    ap = _pad_to(a, (bm, bk))
    bp = _pad_to(b_packed, (bk, bw))
    out = _bitpack.bitpack_matmul(ap, bp, bm=bm, bk=bk, bw=bw,
                                  interpret=interpret)
    return out[:m, :w]


@functools.partial(jax.jit, static_argnames=("interpret",))
def frontier_step(frontier, A, label, interpret: Optional[bool] = None):
    interpret = _interpret(interpret)
    B, V = frontier.shape
    bb, bk = min(128, B), min(128, V)
    fp = _pad_to(frontier, (bb, bk))
    Ap = _pad_to(A, (A.shape[0], bk, bk))
    out = _lf.frontier_step(fp, Ap, label, bb=min(128, fp.shape[0]),
                            bk=min(128, Ap.shape[1]),
                            bn=min(128, Ap.shape[2]), interpret=interpret)
    return out[:B, :V]


@functools.partial(jax.jit, static_argnames=("interpret",))
def frontier_wave_packed(frontier, A, labels,
                         interpret: Optional[bool] = None):
    """One per-row-label frontier wave, returned bit-packed
    (``(R, V // 32)`` uint32): the device build's round trip."""
    return _bitpack.pack_bits(_lf.frontier_step_many(
        frontier, A, labels, interpret=_interpret(interpret)))


pack_bits = _bitpack.pack_bits
unpack_bits = _bitpack.unpack_bits
