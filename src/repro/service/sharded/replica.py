"""Per-shard replica sets with round-robin reads and atomic hot-swap.

Each shard holds ``num_replicas`` interchangeable :class:`ShardReplica`
objects — a frozen slice, its (optional) device layout, and a
:class:`BatchExecutor` over them. Read traffic round-robins across
replicas (:meth:`ShardReplicaSet.acquire`); a rebuild swaps replicas in
*rolling* fashion: the replacement is fully constructed (freeze + device
transfer + executor) before a single reference assignment publishes it,
so a reader that acquired the old replica finishes its batch on a
consistent index while new acquires already see the new generation —
there is never a moment when a replica is half-swapped.

When the host exposes multiple JAX devices, each shard's device arrays are
placed round-robin across them (`shard_id % len(devices)`) — in-process
workers standing in for real multi-host placement. A layout that cannot be
built or placed raises; nothing serves in its place.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.core.minimum_repeat import LabelSeq
from repro.core.rlc_index import FrozenRLCIndex, RLCIndex

from ..executor import BatchExecutor


def dict_index_slice(frozen_slice: FrozenRLCIndex, lo: int, hi: int,
                     id_to_mr: Sequence[LabelSeq]) -> RLCIndex:
    """Shard-local dict-layout index reconstructed from a frozen slice.

    The python-fallback twin of :meth:`FrozenRLCIndex.slice_rows`: entry
    dicts populated only for rows ``[lo, hi)``, global vertex ids and
    ``aid`` kept. This is what a shard-host *worker process* serves its
    python path from — a host never materializes the global dict index
    (:mod:`repro.service.rpc.worker`); the routing invariant guarantees
    every locally executed query has both endpoints in range.
    """
    n = frozen_slice.num_vertices
    l_out: List[dict] = [dict() for _ in range(n)]
    l_in: List[dict] = [dict() for _ in range(n)]

    def fill(maps, indptr, hub, mr):
        for v in range(lo, hi):
            a, b = int(indptr[v]), int(indptr[v + 1])
            d = maps[v]
            for h, m in zip(hub[a:b], mr[a:b]):
                d.setdefault(int(h), set()).add(tuple(id_to_mr[int(m)]))

    fill(l_out, frozen_slice.out_indptr, frozen_slice.out_hub,
         frozen_slice.out_mr)
    fill(l_in, frozen_slice.in_indptr, frozen_slice.in_hub,
         frozen_slice.in_mr)
    return RLCIndex(n, frozen_slice.k,
                    np.asarray(frozen_slice.aid, dtype=np.int64),
                    l_in=l_in, l_out=l_out)


@dataclasses.dataclass
class ShardReplica:
    """One serveable copy of a shard: frozen slice + device layout +
    executor."""

    shard_id: int
    replica_id: int
    generation: int
    frozen: FrozenRLCIndex          # slice view: rows [lo, hi) populated
    device_index: Optional[object]  # DeviceIndex, or None (use_device off)
    executor: BatchExecutor
    device: Optional[object] = None  # jax.Device this replica is pinned to


def _pin(device_index, device):
    """Move a DeviceIndex's arrays onto ``device`` (None: leave them on
    the default device)."""
    if device is None:
        return device_index
    import jax
    put = lambda a: jax.device_put(a, device)  # noqa: E731
    return dataclasses.replace(
        device_index,
        out_hub=put(device_index.out_hub),
        out_mr=put(device_index.out_mr),
        in_hub=put(device_index.in_hub),
        in_mr=put(device_index.in_mr),
        out_key=put(device_index.out_key),
        in_key=put(device_index.in_key))


def build_device_layout(frozen_slice: FrozenRLCIndex, mr_ids,
                        rows: Optional[Tuple[int, int]] = None,
                        device=None):
    """Row-windowed device layout for one shard slice. Built once per
    (shard, generation) and shared by every replica pinned to the same
    device — the arrays are immutable."""
    from repro.core.device_index import DeviceIndex
    return _pin(DeviceIndex.from_frozen(frozen_slice, mr_ids, rows=rows),
                device)


def build_replica(shard_id: int, replica_id: int, generation: int,
                  frozen_slice: FrozenRLCIndex, mr_ids,
                  index: RLCIndex, id_to_mr: Sequence[LabelSeq],
                  backend: str = "auto", use_device: bool = True,
                  device=None,
                  rows: Optional[Tuple[int, int]] = None,
                  shared_device_index=None, obs=None) -> ShardReplica:
    """Fully construct one replica (the unit hot-swap publishes).

    ``rows=(lo, hi)`` is the shard's vertex range: the device layout packs
    only that row window, so per-shard device memory shrinks ~1/S. Pass
    ``shared_device_index`` (from :func:`build_device_layout`) to reuse one
    immutable layout across a shard's replicas instead of re-packing it
    per replica. ``index``/``id_to_mr`` are the global dict-layout
    reference — the always-available python fallback; the simulated hosts
    share it in-process, a real deployment would ship each shard a slice
    of it.
    """
    device_index = None
    if use_device:
        device_index = (shared_device_index
                        if shared_device_index is not None
                        else build_device_layout(frozen_slice, mr_ids,
                                                 rows=rows, device=device))
    executor = BatchExecutor(index, frozen_slice, device_index,
                             id_to_mr, backend=backend, obs=obs,
                             shard=str(shard_id))
    return ShardReplica(shard_id, replica_id, generation, frozen_slice,
                        device_index, executor, device)


class ShardReplicaSet:
    """All replicas of one shard; round-robin reads, rolling hot-swap."""

    def __init__(self, shard_id: int, lo: int, hi: int,
                 replicas: List[ShardReplica], obs=None):
        if not replicas:
            raise ValueError(f"shard {shard_id} needs >= 1 replica")
        self.shard_id = shard_id
        self.lo = lo
        self.hi = hi
        self.replicas = replicas
        self._rr = itertools.count()
        self._swap_lock = threading.Lock()
        #: True while :meth:`swap` is rebuilding this shard's replicas —
        #: the fan-out's signal to degrade new sub-batches to the online
        #: BiBFS fallback instead of racing the rolling publish
        self.swapping = False
        self.swaps = 0
        self.last_build_backend: Optional[str] = None
        self.obs = obs
        # Executors are rebuilt on every hot-swap, which used to zero their
        # per-shard fallback counts mid-stream; swap() banks the outgoing
        # replicas' counts here so attribution survives the generation.
        self._carried_fallbacks = 0
        self._carried_batches: dict = {}
        self._carried_queries: dict = {}

    @property
    def num_replicas(self) -> int:
        return len(self.replicas)

    @property
    def generation(self) -> int:
        return min(r.generation for r in self.replicas)

    def acquire(self) -> ShardReplica:
        """Round-robin pick; the returned replica stays valid for the whole
        batch even if a swap lands meanwhile (old object keeps serving)."""
        return self.replicas[next(self._rr) % len(self.replicas)]

    def swap(self, generation: int, frozen_slice: FrozenRLCIndex, mr_ids,
             index: RLCIndex, id_to_mr: Sequence[LabelSeq],
             backend: str = "auto", use_device: bool = True,
             build_backend: Optional[str] = None) -> None:
        """Rolling replace of every replica with a freshly built one.
        ``build_backend`` records which :mod:`repro.build` backend
        produced the incoming index (surfaced in :meth:`stats`)."""
        with self._swap_lock:
            self.swapping = True
            try:
                self._swap_locked(generation, frozen_slice, mr_ids, index,
                                  id_to_mr, backend, use_device,
                                  build_backend)
            finally:
                self.swapping = False

    def _swap_locked(self, generation, frozen_slice, mr_ids, index,
                     id_to_mr, backend, use_device, build_backend) -> None:
        self.last_build_backend = build_backend
        # one device pack per (shard, generation, device); replicas on
        # the same device share the immutable layout
        layouts = {}
        if use_device:
            for old in self.replicas:
                if old.device not in layouts:
                    layouts[old.device] = build_device_layout(
                        frozen_slice, mr_ids, rows=(self.lo, self.hi),
                        device=old.device)
        for i, old in enumerate(list(self.replicas)):
            fresh = build_replica(
                self.shard_id, old.replica_id, generation, frozen_slice,
                mr_ids, index, id_to_mr, backend=backend,
                use_device=use_device, device=old.device,
                rows=(self.lo, self.hi),
                shared_device_index=layouts.get(old.device),
                obs=self.obs)
            # bank the outgoing replica's counters before the publish:
            # the fresh executor starts at zero, the set-level totals
            # must not
            self._carried_fallbacks += old.executor.fallbacks
            for b, rec in old.executor.recorders.items():
                if rec.batches:
                    self._carried_batches[b] = (
                        self._carried_batches.get(b, 0) + rec.batches)
                    self._carried_queries[b] = (
                        self._carried_queries.get(b, 0) + rec.queries)
            # single reference assignment = the atomic publish point
            self.replicas[i] = fresh
        self.swaps += 1

    @property
    def fallbacks(self) -> int:
        """Fallback batches attributed to this shard across *all*
        generations: counts banked at swap time plus the live replicas'."""
        return self._carried_fallbacks + sum(
            r.executor.fallbacks for r in self.replicas)

    def backend_totals(self) -> dict:
        """Per-backend ``{batches, queries}`` across generations."""
        out = {b: dict(batches=n, queries=self._carried_queries.get(b, 0))
               for b, n in self._carried_batches.items()}
        for r in self.replicas:
            for b, rec in r.executor.recorders.items():
                if rec.batches:
                    d = out.setdefault(b, dict(batches=0, queries=0))
                    d["batches"] += rec.batches
                    d["queries"] += rec.queries
        return out

    def stats(self) -> dict:
        r0 = self.replicas[0]
        return dict(
            shard=self.shard_id,
            lo=self.lo, hi=self.hi,
            vertices=self.hi - self.lo,
            entries=r0.frozen.num_entries(),
            size_bytes=r0.frozen.size_bytes(),
            replicas=self.num_replicas,
            generation=self.generation,
            swaps=self.swaps,
            fallbacks=self.fallbacks,
            backends=self.backend_totals(),
            build_backend=self.last_build_backend,
            device=r0.device_index is not None,
            row_len=(r0.device_index.row_len
                     if r0.device_index is not None else None),
        )
