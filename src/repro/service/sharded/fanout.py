"""Scatter/gather batch executor over shard replica sets.

Takes one admitted micro-batch (:class:`repro.service.scheduler.Batch`),
regroups its requests into per-``(shard_s, shard_t)`` sub-batches, runs
each sub-batch on the owning shard's replicas, and gathers the answers
back into admission order:

* **same-shard** ``(i, i)`` — the full multi-backend
  :class:`BatchExecutor` of one replica of shard *i* (pallas / XLA-sorted /
  frozen-numpy / python with fallback), exactly the single-host path but
  over the shard's slice;
* **cross-shard** ``(i, j)`` — the *scatter* hop: a replica of shard *i*
  gathers the padded out-row digests of the batch's source vertices and
  ships them to shard *j*'s device (simulated one-hop transfer;
  ``jax.device_put`` when the shards are pinned to different devices),
  where :func:`repro.core.device_index.join_rows` merge-joins digests
  against *j*'s local in-rows. Without device layouts (``use_device``
  off) the same join runs row-by-row through
  :func:`repro.core.rlc_index.merge_join_rows`.

Sub-batches are padded to the next power of two (capped at the admission
batch size) by repeating their first request, so each shard pair sees a
small, bounded set of jit shapes instead of one per sub-batch length.

When either side's replica set is mid-swap (``ShardReplicaSet.swapping``),
the sub-batch gracefully degrades to the online BiBFS fallback on the
live graph instead of racing the rolling publish — exact answers (BiBFS
is the oracle), just slower, counted in ``rlc_fanout_degraded``. Requires
the executor to be constructed with ``graph``/``id_to_mr``; without them
the degrade path is unavailable and sub-batches acquire replicas as
before.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np

from repro.core.rlc_index import merge_join_rows
from repro.obs import NULL_OBS

from ..metrics import LatencyRecorder
from ..rpc.controller import WorkerLost
from ..scheduler import Batch
from .replica import ShardReplica, ShardReplicaSet
from .router import TwoSidedRouter


def _pad_pow2(vals: List[int], cap: int) -> np.ndarray:
    """Pad to the next power of two (<= cap) by repeating the first value."""
    n = len(vals)
    size = 1
    while size < n:
        size *= 2
    size = min(size, cap) if cap >= n else n
    out = np.full(size, vals[0], dtype=np.int32)
    out[:n] = np.asarray(vals, dtype=np.int32)
    return out


class ScatterGatherExecutor:
    #: sub-batch failures the BiBFS degrade path answers: a lost worker
    #: process under the RPC transport. In-process shards have no
    #: transport to lose, so any failure there is a fault and raises.
    transport_errors: Tuple[type, ...] = ()

    def __init__(self, shards: List[ShardReplicaSet],
                 router: TwoSidedRouter, batch_size: int, obs=None,
                 graph=None, id_to_mr=None):
        self.shards = shards
        self.router = router
        self.batch_size = batch_size
        self.graph = graph          # live graph for the BiBFS degrade path
        self.id_to_mr = id_to_mr
        self.recorders = dict(local=LatencyRecorder("local"),
                              remote=LatencyRecorder("remote"))
        self.sub_batches: Dict[Tuple[int, int], int] = {}
        self.remote_joins_device = 0
        self.remote_joins_numpy = 0
        self.degraded = 0       # sub-batches answered by BiBFS mid-swap
        self.digest_bytes = 0   # simulated cross-host traffic
        self.obs = obs or NULL_OBS
        reg = self.obs.registry
        sub = reg.histogram(
            "rlc_fanout_subbatch_seconds",
            desc="wall time of one per-(shard_s, shard_t) sub-batch",
            unit="s", labelnames=("path",))
        self._m_sub = {p: sub.labels(path=p) for p in ("local", "remote")}
        self._m_digest = reg.counter(
            "rlc_fanout_digest_bytes",
            desc="simulated cross-shard digest traffic", unit="By").labels()
        joins = reg.counter("rlc_fanout_remote_joins",
                            desc="cross-shard digest joins by path",
                            labelnames=("path",))
        self._m_join = {p: joins.labels(path=p)
                        for p in ("device", "numpy")}
        self._m_degraded = reg.counter(
            "rlc_fanout_degraded",
            desc="sub-batches degraded to online BiBFS because a shard "
                 "replica set was mid-swap").labels()

    def _degrade_bibfs(self, reqs, idxs) -> np.ndarray:
        """Answer one sub-batch by online bidirectional BFS on the live
        graph — the mid-swap fallback. Exact (BiBFS is the oracle), so
        answers stay bit-identical to the index path."""
        from repro.core.baselines import bibfs_rlc
        out = np.zeros(len(idxs), dtype=bool)
        for j, q in enumerate(idxs):
            r = reqs[q]
            out[j] = bibfs_rlc(self.graph, r.s, r.t,
                               self.id_to_mr[r.mr_id])
        self.degraded += 1
        self._m_degraded.inc()
        return out

    # -- transport hooks (overridden by the RPC executor) --------------- #
    def _swapping(self, shard_id: int) -> bool:
        """True when ``shard_id`` cannot take a sub-batch right now (its
        replica set is mid-swap) and the degrade path should answer."""
        return self.shards[shard_id].swapping

    def _run_sub(self, ss: int, st: int, s: np.ndarray, t: np.ndarray,
                 mr: np.ndarray, n_real: int,
                 trace=None) -> Tuple[np.ndarray, str]:
        """Execute one padded ``(shard_s, shard_t)`` sub-batch; returns
        ``(answers[:n_real], backend_label)``. The in-process transport
        acquires replicas directly; :class:`RpcScatterGatherExecutor`
        sends the same sub-batch to worker processes."""
        if ss == st:
            rep = self.shards[st].acquire()
            ans, backend = rep.executor.execute(s, t, mr, n_real=n_real,
                                                trace=trace)
            return np.asarray(ans[:n_real], dtype=bool), backend
        ans = self._cross_shard(ss, st, s, t, mr, n_real, trace=trace)
        return np.asarray(ans[:n_real], dtype=bool), "digest"

    # ------------------------------------------------------------------ #
    def execute(self, batch: Batch,
                trace=None) -> Tuple[np.ndarray, List[str]]:
        """Answer every real request of ``batch``, in admission order.
        Returns ``(answers, backends)``: the bool answers plus one
        backend-attribution label per request (same order) for the typed
        :class:`~repro.service.answer.Answer` results. ``trace``:
        optional sampled :class:`repro.obs.Trace` — the shard route,
        each sub-batch, and the digest hand-off get spans."""
        reqs = batch.requests
        t_route = time.perf_counter()
        groups: Dict[Tuple[int, int], List[int]] = {}
        for q, r in enumerate(reqs):
            route = self.router.route(r.s, r.t)
            groups.setdefault((route.shard_s, route.home), []).append(q)
        if trace is not None:
            dt = time.perf_counter() - t_route
            trace.add("route", trace.tracer._now() - dt, dt, cat="fanout",
                      n=len(reqs), sub_batches=len(groups))
        answers = np.zeros(len(reqs), dtype=bool)
        backends: List[str] = [""] * len(reqs)
        for (ss, st), idxs in sorted(groups.items()):
            self.sub_batches[(ss, st)] = self.sub_batches.get((ss, st), 0) + 1
            can_degrade = (self.graph is not None
                           and self.id_to_mr is not None)
            if can_degrade and (self._swapping(ss) or self._swapping(st)):
                t0 = time.perf_counter()
                ans = self._degrade_bibfs(reqs, idxs)
                dt = time.perf_counter() - t0
                self.recorders["local"].record(dt, len(idxs))
                if trace is not None:
                    trace.add(f"sub[{ss}->{st}]",
                              trace.tracer._now() - dt, dt, cat="fanout",
                              n=len(idxs), path="degraded")
                answers[np.asarray(idxs)] = ans
                for q in idxs:
                    backends[q] = "bibfs"
                continue
            s = _pad_pow2([reqs[q].s for q in idxs], self.batch_size)
            t = _pad_pow2([reqs[q].t for q in idxs], self.batch_size)
            mr = _pad_pow2([reqs[q].mr_id for q in idxs], self.batch_size)
            t0 = time.perf_counter()
            try:
                ans, backend = self._run_sub(ss, st, s, t, mr, len(idxs),
                                             trace=trace)
            except self.transport_errors:
                # every worker of a shard died mid-call: the degrade path
                # still answers exactly
                if not can_degrade:
                    raise
                ans, backend = self._degrade_bibfs(reqs, idxs), "bibfs"
            path = "local" if ss == st else "remote"
            dt = time.perf_counter() - t0
            self.recorders[path].record(dt, len(idxs))
            self._m_sub[path].observe(dt)
            if trace is not None:
                trace.add(f"sub[{ss}->{st}]", trace.tracer._now() - dt, dt,
                          cat="fanout", n=len(idxs), path=path)
            answers[np.asarray(idxs)] = np.asarray(ans[:len(idxs)],
                                                   dtype=bool)
            for q in idxs:
                backends[q] = backend
        return answers, backends

    # ------------------------------------------------------------------ #
    def _cross_shard(self, ss: int, st: int, s: np.ndarray, t: np.ndarray,
                     mr: np.ndarray, n_real: int,
                     trace=None) -> np.ndarray:
        """Digest scatter from shard ``ss`` + merge-join at shard ``st``.

        ``s``/``t``/``mr`` are shape-padded; only the first ``n_real``
        entries are real queries (padding exists solely to bound jit
        shapes on the device path — the numpy path and the traffic
        accounting skip it).
        """
        src = self.shards[ss].acquire()
        dst = self.shards[st].acquire()
        if src.device_index is not None and dst.device_index is not None:
            ans = self._join_device(src, dst, s, t, mr, n_real)
            self.remote_joins_device += 1
            self._m_join["device"].inc()
            return ans[:n_real]
        self.remote_joins_numpy += 1
        self._m_join["numpy"].inc()
        return self._join_numpy(src, dst, s[:n_real], t[:n_real],
                                mr[:n_real])

    def _join_device(self, src: ShardReplica, dst: ShardReplica,
                     s, t, mr, n_real: int) -> np.ndarray:
        import jax
        from repro.core.device_index import join_rows
        oh, om = src.device_index.gather_out_rows(s)
        if src.device is not None and src.device != dst.device:
            # the one-hop digest ship (real transfer when pinned apart)
            oh = jax.device_put(oh, dst.device)
            om = jax.device_put(om, dst.device)
        ih, im = dst.device_index.gather_in_rows(t)
        import jax.numpy as jnp
        ans = np.asarray(join_rows(oh, om, ih, im,
                                   jnp.asarray(s, jnp.int32),
                                   jnp.asarray(t, jnp.int32),
                                   jnp.asarray(mr, jnp.int32)))
        # traffic accounting counts real rows only; padding ships just
        # for the jit shape
        nbytes = 2 * n_real * int(oh.shape[1]) * 4
        self.digest_bytes += nbytes
        self._m_digest.inc(nbytes)
        return ans

    def _join_numpy(self, src: ShardReplica, dst: ShardReplica,
                    s, t, mr) -> np.ndarray:
        out = np.zeros(len(s), dtype=bool)
        aid = src.frozen.aid
        for q in range(len(s)):
            oh, om = src.frozen.row_out(int(s[q]))     # the digest
            ih, im = dst.frozen.row_in(int(t[q]))
            self.digest_bytes += (oh.nbytes + om.nbytes)
            self._m_digest.inc(oh.nbytes + om.nbytes)
            out[q] = merge_join_rows(oh, om, ih, im, aid,
                                     int(s[q]), int(t[q]), int(mr[q]))
        return out

    # ------------------------------------------------------------------ #
    def stats(self) -> dict:
        return dict(
            local=self.recorders["local"].summary(),
            remote=self.recorders["remote"].summary(),
            sub_batches={f"{a}->{b}": c
                         for (a, b), c in sorted(self.sub_batches.items())},
            remote_joins_device=self.remote_joins_device,
            remote_joins_numpy=self.remote_joins_numpy,
            degraded=self.degraded,
            digest_bytes=self.digest_bytes,
        )


class RpcScatterGatherExecutor(ScatterGatherExecutor):
    """The same scatter/gather, but every sub-batch crosses a process
    boundary: same-shard work goes to a shard-host worker over RPC
    (``transport="rpc"``), and the cross-shard digest hand-off gathers
    out-row digests from shard *i*'s worker and ships the *bytes* to
    shard *j*'s worker for the merge join — the wire replacing
    ``jax.device_put``.

    Inherits routing, padding, accounting, tracing, and the BiBFS
    degrade path; only the three transport hooks differ. A shard is
    "swapping" here when no live, unfenced worker can serve it (the
    cluster fences workers one at a time during a rolling swap, so with
    replicas > 1 this almost never degrades). A :class:`WorkerLost`
    escaping a sub-batch is caught by the base class and answered by
    BiBFS — exact answers survive total shard loss.
    """

    transport_errors = (WorkerLost,)

    def __init__(self, cluster, router: TwoSidedRouter, batch_size: int,
                 obs=None, graph=None, id_to_mr=None):
        # the base class wants replica sets; the cluster stands in for
        # them — shards=[] keeps every inherited in-process path unused
        super().__init__([], router, batch_size, obs=obs, graph=graph,
                         id_to_mr=id_to_mr)
        self.cluster = cluster
        self.remote_joins_rpc = 0

    def _swapping(self, shard_id: int) -> bool:
        return self.cluster.swapping(shard_id)

    def _run_sub(self, ss: int, st: int, s: np.ndarray, t: np.ndarray,
                 mr: np.ndarray, n_real: int,
                 trace=None) -> Tuple[np.ndarray, str]:
        if ss == st:
            ans, backend = self.cluster.execute(st, s, t, mr, n_real)
            return np.asarray(ans[:n_real], dtype=bool), f"rpc:{backend}"
        # scatter: shard ss's worker gathers out-row digests ...
        digest = self.cluster.gather_digest(ss, s[:n_real])
        nbytes = int(digest["hub"].nbytes + digest["mr"].nbytes)
        # ... which cross the wire (real bytes, not simulated) ...
        self.digest_bytes += nbytes
        self._m_digest.inc(nbytes)
        if trace is not None:
            trace.add(f"digest[{ss}->{st}]", trace.tracer._now(), 0.0,
                      cat="fanout", bytes=nbytes)
        # ... and shard st's worker merge-joins them against its in-rows
        ans = self.cluster.join_digest(st, s[:n_real], t[:n_real],
                                       mr[:n_real], digest)
        self.remote_joins_rpc += 1
        self._m_join["numpy"].inc()
        return np.asarray(ans[:n_real], dtype=bool), "rpc:digest"

    def stats(self) -> dict:
        st = super().stats()
        st["remote_joins_rpc"] = self.remote_joins_rpc
        st["rpc"] = self.cluster.stats()
        return st
