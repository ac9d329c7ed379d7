"""Batched RLC query evaluation on device (serving path).

The frozen index is laid out as padded per-vertex rows sorted by
``(aid(hub), mr_id)`` — Algorithm 1's merge-join order. A query batch
``(s, t, mr)`` evaluates Case 2 (direct entry) and Case 1 (hub join) with
pure vectorized compares; the hot loop optionally dispatches to the Pallas
merge-join kernel (:mod:`repro.kernels.mergejoin`).

Row padding uses hub id ``-1`` (never matches a real hub / query vertex).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .constants import PAD
from .minimum_repeat import LabelSeq, mr_id_space
from .rlc_index import FrozenRLCIndex, RLCIndex


@dataclass
class DeviceIndex:
    """Padded dense layout: (n, E) hub-id and mr-id arrays per direction.

    Two query formulations:
      * dense — (E x E) broadcast join per query (VPU-friendly inside the
        Pallas kernel where the tile stays in VMEM);
      * sorted — rows re-encoded as ascending ``hub * C + mr`` keys; the
        join is a vectorized ``searchsorted`` intersection, moving (Q, E)
        instead of (Q, E, E) through HBM — the XLA-lowered serving path.

    A batch reaches the device packed: :meth:`join` takes one ``(3, Q)``
    int32 host array (rows ``s``, ``t``, ``mr``) and hands it to one
    jitted entry that slices it on the device, so a batch costs one
    host-to-device transfer, made by the jitted call itself.

    With ``row_lo > 0`` the arrays hold only the vertex-row window
    ``[row_lo, row_lo + rows)`` (a shard's slice): query/hub *ids* stay
    global, only row storage is windowed — a shard's device memory then
    really is ~1/S of the whole index. Callers must only query vertices
    inside the window (the sharded router's contract).
    """

    num_vertices: int
    k: int
    row_len: int
    out_hub: jax.Array  # (rows, E) int32, PAD-filled
    out_mr: jax.Array   # (rows, E) int32
    in_hub: jax.Array
    in_mr: jax.Array
    mr_ids: Dict[LabelSeq, int]
    num_mrs: int = 0
    out_key: Optional[jax.Array] = None  # (rows, E) int32 sorted asc
    in_key: Optional[jax.Array] = None
    row_lo: int = 0     # first vertex id stored; ids below/above are
                        # outside this window (other shards)

    @staticmethod
    def from_index(idx: RLCIndex, num_labels: int,
                   row_len: Optional[int] = None,
                   pad_to_multiple: int = 8) -> "DeviceIndex":
        ids = mr_id_space(num_labels, idx.k)
        return DeviceIndex.from_frozen(idx.freeze(ids), ids,
                                       row_len=row_len,
                                       pad_to_multiple=pad_to_multiple)

    @staticmethod
    def from_frozen(frozen: FrozenRLCIndex, mr_ids: Dict[LabelSeq, int],
                    row_len: Optional[int] = None,
                    pad_to_multiple: int = 8,
                    rows: Optional[Tuple[int, int]] = None) -> "DeviceIndex":
        """Device transfer of an already-frozen index (the service path
        freezes once and reuses the CSR layout for the numpy backend).

        ``rows=(lo, hi)`` packs only that vertex-row window — pair it with
        :meth:`FrozenRLCIndex.slice_rows` so a shard's device arrays cover
        just the rows it owns instead of full height.
        """
        E = row_len or max(1, frozen.max_row)
        E = ((E + pad_to_multiple - 1) // pad_to_multiple) * pad_to_multiple
        lo, hi = (0, frozen.num_vertices) if rows is None else rows
        if not (0 <= lo <= hi <= frozen.num_vertices):
            raise ValueError(
                f"rows [{lo}, {hi}) out of range "
                f"[0, {frozen.num_vertices}]")

        def pack(indptr, hub, mr):
            H = np.full((hi - lo, E), PAD, np.int32)
            M = np.full((hi - lo, E), PAD, np.int32)
            for v in range(lo, hi):
                a, b = indptr[v], indptr[v + 1]
                ln = min(b - a, E)
                H[v - lo, :ln] = hub[a:a + ln]
                M[v - lo, :ln] = mr[a:a + ln]
            return jnp.asarray(H), jnp.asarray(M)

        oh, om = pack(frozen.out_indptr, frozen.out_hub, frozen.out_mr)
        ih, im = pack(frozen.in_indptr, frozen.in_hub, frozen.in_mr)
        C = len(mr_ids)

        def keys(hub, mr):
            h = np.asarray(hub)
            m = np.asarray(mr)
            key = np.where(h == PAD, np.iinfo(np.int32).max,
                           h.astype(np.int64) * C + m).astype(np.int32)
            return jnp.asarray(np.sort(key, axis=1))

        return DeviceIndex(frozen.num_vertices, frozen.k, E, oh, om, ih, im,
                           mr_ids, C, keys(oh, om), keys(ih, im), lo)

    # ---------------------------------------------------------------- #
    def query_batch(self, s: np.ndarray, t: np.ndarray, mr: np.ndarray,
                    use_pallas: bool = False,
                    method: str = "dense") -> np.ndarray:
        """Answer a batch on the device and read the answers back: the
        synchronous form of :meth:`join`."""
        q = np.stack([s, t, mr]).astype(np.int32)
        return np.asarray(self.join(q, "pallas" if use_pallas else method))

    def join(self, q: np.ndarray, method: str = "dense") -> jax.Array:
        """Dispatch the join of a packed ``(3, Q)`` int32 host batch
        (rows ``s``, ``t``, ``mr``); returns the (possibly not yet ready)
        boolean answers. The batch reaches the device as the jitted
        call's one host argument: no separate transfer per input.
        ``method``: ``"pallas"`` (the merge-join kernel), ``"sorted"``
        or ``"dense"``."""
        return _join_packed(self.out_hub, self.out_mr, self.in_hub,
                            self.in_mr, self.out_key, self.in_key, q,
                            row_lo=self.row_lo, num_mrs=self.num_mrs,
                            method=method)

    def query(self, s: int, t: int, L: Sequence[int]) -> bool:
        c = self.mr_ids.get(tuple(L))
        if c is None:
            return False
        return bool(self.query_batch(np.array([s]), np.array([t]),
                                     np.array([c]))[0])

    def explain_batch(self, s: np.ndarray, t: np.ndarray, mr: np.ndarray,
                      max_hubs: int = 8) -> list:
        """Witness mode for the device join path: per query, the
        derivation over exactly the padded row digests the kernels join
        (gathered host-side, PAD slots dropped). Device rows carry no
        access-id table, so join hubs report ``aid: null`` and sort by
        vertex id; row lengths reflect the ``row_len`` truncation the
        device layout actually serves with."""
        from repro.obs.explain import explain_rows
        s = np.asarray(s)
        t = np.asarray(t)
        mr = np.asarray(mr)
        oh, om = self.gather_out_rows(s)
        ih, im = self.gather_in_rows(t)
        oh, om = np.asarray(oh), np.asarray(om)
        ih, im = np.asarray(ih), np.asarray(im)
        return [explain_rows(oh[q], om[q], ih[q], im[q],
                             int(s[q]), int(t[q]), int(mr[q]),
                             pad=PAD, max_hubs=max_hubs)
                for q in range(len(s))]

    # -- shard scatter/gather helpers -------------------------------------- #
    def gather_out_rows(self, s: np.ndarray) -> Tuple[jax.Array, jax.Array]:
        """Padded ``(Q, E)`` out-row digests for a batch of source vertices
        — what a shard ships to the in-side owner for a cross-shard join
        (:func:`join_rows`). ``s`` is in global vertex ids."""
        s = jnp.asarray(s, jnp.int32) - self.row_lo
        return self.out_hub[s], self.out_mr[s]

    def gather_in_rows(self, t: np.ndarray) -> Tuple[jax.Array, jax.Array]:
        t = jnp.asarray(t, jnp.int32) - self.row_lo
        return self.in_hub[t], self.in_mr[t]


@functools.partial(jax.jit, static_argnames=("row_lo", "num_mrs",
                                             "method"))
def _join_packed(out_hub, out_mr, in_hub, in_mr, out_key, in_key, q, *,
                 row_lo: int, num_mrs: int, method: str):
    """The serving join as one jit boundary: ``q`` is the packed
    ``(3, Q)`` batch, sliced into ``s``, ``t``, ``mr`` on the device;
    ``row_lo`` offsets the storage rows of a row-windowed layout."""
    s, t, mr = q[0], q[1], q[2]
    if method == "pallas":
        from repro.kernels import ops
        return ops.mergejoin_query(out_hub, out_mr, in_hub, in_mr, s, t, mr,
                                   row_base_out=row_lo, row_base_in=row_lo)
    if method == "sorted":
        return _query_batch_sorted_rows(out_key, in_key, s - row_lo,
                                        t - row_lo, s, t, mr, num_mrs)
    if method == "dense":
        return _query_batch_rows(out_hub, out_mr, in_hub, in_mr,
                                 s - row_lo, t - row_lo, s, t, mr)
    raise ValueError(f"unknown join method {method!r}")


@jax.jit
def join_rows(oh, om, ih, im, s, t, mr):
    """Batched Algorithm 1 on pre-gathered rows.

    ``oh/om`` are (Q, Eo) out-rows of each query's ``s``; ``ih/im`` are
    (Q, Ei) in-rows of each query's ``t`` (Eo and Ei may differ — e.g. two
    shards with different row paddings). Case 2 via direct compares, Case 1
    via an (Eo x Ei) broadcast join (rows are aid-sorted; the dense compare
    is the merge join's VPU-friendly analog). Separated from the row gather
    so the sharded fan-out path can join a shipped digest against local
    in-rows without materializing one global index.
    """
    q_mr = mr[:, None]
    case2 = jnp.any((oh == t[:, None]) & (om == q_mr), axis=1) | \
        jnp.any((ih == s[:, None]) & (im == q_mr), axis=1)
    o_ok = (om == q_mr) & (oh != PAD)            # (Q, Eo)
    i_ok = (im == q_mr) & (ih != PAD)            # (Q, Ei)
    join = (oh[:, :, None] == ih[:, None, :]) & \
        o_ok[:, :, None] & i_ok[:, None, :]      # (Q, Eo, Ei)
    case1 = jnp.any(join, axis=(1, 2))
    return case2 | case1


@jax.jit
def _query_batch_rows(out_hub, out_mr, in_hub, in_mr, s_row, t_row,
                      s, t, mr):
    """Row-windowed batched Algorithm 1: gather by *storage* row index
    (``s_row = s - row_lo``), compare by global vertex id — the shard
    layouts store a window of rows but keep the global id space."""
    return join_rows(out_hub[s_row], out_mr[s_row],
                     in_hub[t_row], in_mr[t_row], s, t, mr)


@jax.jit
def _query_batch_ref(out_hub, out_mr, in_hub, in_mr, s, t, mr):
    """Reference batched Algorithm 1 (also the Pallas kernel oracle):
    gather rows out[s_q], in[t_q], then :func:`join_rows`. Full-height
    (row_lo = 0) layout form, used by
    :func:`repro.core.distributed.distributed_query_batch`."""
    return join_rows(out_hub[s], out_mr[s], in_hub[t], in_mr[t], s, t, mr)


@jax.jit
def _query_batch_sorted_rows(out_key, in_key, s_row, t_row, s, t, mr,
                             num_mrs):
    """Sorted-key intersection join: O(E log E) per query, (Q, E) HBM
    traffic. Key = hub * C + mr;
    PAD rows sort to INT32_MAX and never match. Rows are gathered by
    storage index; key compares use global ids."""
    ok = out_key[s_row]                   # (Q, E) ascending
    ik = in_key[t_row]
    q_mr = mr[:, None]
    # Case 1: out keys with the queried mr present in the in row
    pos = jax.vmap(jnp.searchsorted)(ik, ok)        # (Q, E)
    pos = jnp.minimum(pos, ik.shape[1] - 1)
    hit = jnp.take_along_axis(ik, pos, axis=1) == ok
    mr_match = (ok % num_mrs) == q_mr
    big = jnp.iinfo(jnp.int32).max
    case1 = jnp.any(hit & mr_match & (ok != big), axis=1)
    # Case 2: direct entries (t, mr) in L_out(s) / (s, mr) in L_in(t)
    kt = (t * num_mrs + mr)[:, None]
    ks = (s * num_mrs + mr)[:, None]
    p2 = jax.vmap(jnp.searchsorted)(ok, kt[:, 0][:, None])
    p2 = jnp.minimum(p2, ok.shape[1] - 1)
    c2a = jnp.take_along_axis(ok, p2, axis=1) == kt
    p3 = jax.vmap(jnp.searchsorted)(ik, ks[:, 0][:, None])
    p3 = jnp.minimum(p3, ik.shape[1] - 1)
    c2b = jnp.take_along_axis(ik, p3, axis=1) == ks
    return case1 | jnp.any(c2a, axis=1) | jnp.any(c2b, axis=1)
