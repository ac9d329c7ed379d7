"""Multi-backend batch executor for RLC query batches.

One interface over the four existing engines:

* ``python`` — dict-layout Algorithm 1 (:meth:`RLCIndex.query`), the
  always-available reference;
* ``numpy``  — frozen CSR merge-join (:meth:`FrozenRLCIndex.query_batch`);
* ``sorted`` — XLA sorted-key intersection on the padded device layout
  (:meth:`DeviceIndex.query_batch` with ``method="sorted"``);
* ``pallas`` — the Pallas merge-join kernel (compiled for the TPU; run by
  the Pallas interpreter when JAX is on the CPU).

A backend that is *unavailable* — the device backends without a
:class:`DeviceIndex`, ``numpy`` without the frozen CSR — is skipped for
the first available one in :data:`BACKENDS` order, and the skip is counted
as a fallback. A backend that *fails* is a fault: the executor raises
:class:`ExecutorError` chained to the cause and never hides it behind a
host backend. Per-backend latency/throughput lands in
:class:`repro.service.metrics.LatencyRecorder` and — when an
:class:`repro.obs.Observability` is attached — in the shared metrics
registry (labeled by backend and shard), with a span per batch when the
batch rides a sampled trace. The device backends run in four phase
spans (:meth:`repro.obs.Observability.phase`): ``exec.h2d`` (pad and
stack the inputs into one ``(3, cap)`` int32 host array),
``exec.dispatch`` (the jitted join call, which transfers that array,
and the request for its answers), ``exec.wait`` (until the answers are
ready) and ``exec.d2h`` (the rest of the readback); each device batch
counts one host array in ``rlc_executor_h2d_arrays``.

A batch runs in two steps: :meth:`BatchExecutor.dispatch` starts it
(``exec.h2d``, ``exec.dispatch``) and returns a :class:`PendingBatch`
without waiting for the device; :meth:`PendingBatch.result` waits for
the answers (``exec.wait``, ``exec.d2h``). :meth:`BatchExecutor.execute`
is the two back to back. ``rlc_executor_batch_seconds`` records the
executor's own time on a batch, the two steps together, and not the
time between them.
"""
from __future__ import annotations

import time
from contextlib import nullcontext
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from repro.core.minimum_repeat import LabelSeq
from repro.core.rlc_index import FrozenRLCIndex, RLCIndex
from repro.device import on_cpu
from repro.obs import NULL_OBS

from .metrics import LatencyRecorder

# Preference order: fastest batched path first, reference last.
BACKENDS = ("pallas", "sorted", "numpy", "python")


class ExecutorError(RuntimeError):
    """Raised when no backend is available for a batch, or when the
    backend that ran it failed (chained to the cause)."""


class BatchExecutor:
    def __init__(self, index: RLCIndex,
                 frozen: Optional[FrozenRLCIndex] = None,
                 device_index=None,
                 id_to_mr: Optional[Sequence[LabelSeq]] = None,
                 backend: str = "auto", obs=None, shard: str = "-"):
        if backend != "auto" and backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {backend!r}; choose from "
                f"{('auto',) + BACKENDS}")
        self.index = index
        self.frozen = frozen
        self.device_index = device_index
        self.id_to_mr = list(id_to_mr) if id_to_mr is not None else None
        self.backend = backend
        self.recorders: Dict[str, LatencyRecorder] = {
            b: LatencyRecorder(b) for b in BACKENDS}
        self.fallbacks = 0
        # registry cells, pre-bound per backend (shard = "-" single-host).
        # The registry outlives this executor, so replica hot-swaps never
        # reset the labeled series even though self.fallbacks restarts.
        self.obs = obs or NULL_OBS
        reg = self.obs.registry
        lat = reg.histogram(
            "rlc_executor_batch_seconds",
            desc="executor time on one batch (dispatch plus result), by "
                 "answering backend",
            unit="s", labelnames=("backend", "shard"))
        bat = reg.counter("rlc_executor_batches",
                          desc="batches answered, by backend",
                          labelnames=("backend", "shard"))
        qry = reg.counter("rlc_executor_queries",
                          desc="real (unpadded) queries answered",
                          labelnames=("backend", "shard"))
        self._m_lat = {b: lat.labels(backend=b, shard=shard)
                       for b in BACKENDS}
        self._m_bat = {b: bat.labels(backend=b, shard=shard)
                       for b in BACKENDS}
        self._m_qry = {b: qry.labels(backend=b, shard=shard)
                       for b in BACKENDS}
        h2d = reg.counter("rlc_executor_h2d_arrays",
                          desc="host arrays handed to the device",
                          labelnames=("backend", "shard"))
        self._m_h2d = {b: h2d.labels(backend=b, shard=shard)
                       for b in BACKENDS}
        self._m_fallback = reg.counter(
            "rlc_executor_fallbacks",
            desc="batches not answered by the first-choice backend",
            labelnames=("from", "to", "shard"))
        self._shard = shard
        self._ph_h2d, self._ph_dispatch, self._ph_wait, self._ph_d2h = (
            self.obs.phase(f"exec.{p}", cat="executor")
            for p in ("h2d", "dispatch", "wait", "d2h"))

    # ------------------------------------------------------------------ #
    def available(self, backend: str) -> bool:
        if backend in ("pallas", "sorted"):
            return self.device_index is not None
        if backend == "numpy":
            return self.frozen is not None
        if backend == "python":
            return self.id_to_mr is not None
        return False

    def resolve(self, backend: Optional[str] = None) -> str:
        """Map ``auto`` (or None) to the best available backend."""
        b = backend or self.backend
        if b == "auto":
            order = BACKENDS
            if on_cpu():
                # the Pallas kernel only *interprets* on CPU — the XLA
                # sorted-key path is the fast lowering there.
                order = ("sorted", "numpy", "pallas", "python")
            for cand in order:
                if self.available(cand):
                    return cand
            raise ExecutorError("no backend available")
        return b

    # ------------------------------------------------------------------ #
    def dispatch(self, s: np.ndarray, t: np.ndarray, mr_id: np.ndarray,
                 n_real: Optional[int] = None,
                 backend: Optional[str] = None,
                 trace=None) -> "PendingBatch":
        """Start answering a padded batch; returns a :class:`PendingBatch`
        whose :meth:`~PendingBatch.result` gives ``(answers[:n_real],
        backend)``.

        A device backend packs the batch, makes the jitted join call and
        queues the readback, then returns without waiting for the device
        (``exec.h2d``, ``exec.dispatch``); the host backends answer at
        once and return a handle that is already complete. Runs the
        requested backend, or the first available one in ``BACKENDS``
        order when it is unavailable (a fallback, counted once the batch
        is answered). A backend that raises, here or in ``result()``, is
        not retried elsewhere: the error propagates as
        :class:`ExecutorError`. ``trace``: optional
        :class:`repro.obs.Trace`; the dispatch gets an ``exec:<backend>``
        span (``args.error`` on a failed one), the phases nested inside.
        """
        first = self.resolve(backend)
        b = next((c for c in (first,) + BACKENDS if self.available(c)),
                 None)
        if b is None:
            raise ExecutorError(f"no backend available for {first!r}")
        n = len(s) if n_real is None else int(n_real)
        span = (trace.span(f"exec:{b}", cat="executor", n=n,
                           fallback=b != first)
                if trace is not None else nullcontext())
        t0 = time.perf_counter()
        try:
            with span:
                out = self._launch(b, s, t, mr_id, n, trace)
        except Exception as e:
            raise _failed(b, n) from e
        return PendingBatch(self, first, b, n, out, trace,
                            time.perf_counter() - t0)

    def execute(self, s: np.ndarray, t: np.ndarray, mr_id: np.ndarray,
                n_real: Optional[int] = None,
                backend: Optional[str] = None,
                trace=None, pending: Optional["PendingBatch"] = None
                ) -> Tuple[np.ndarray, str]:
        """Answer a padded batch; returns ``(answers[:n_real], backend)``:
        :meth:`dispatch`, then wait for the result. ``pending``: the
        handle :meth:`dispatch` already returned for this batch, so that
        only the wait is left. Every answer a facade serves comes out of
        here, however its batch was started, so a wrapper around this
        method (a profiler span, an injected fault) sees each batch."""
        if pending is None:
            pending = self.dispatch(s, t, mr_id, n_real, backend, trace)
        ans, b = pending.result()
        n = len(s) if n_real is None else int(n_real)
        return ans[:n], b

    def _answered(self, first: str, b: str, n: int, seconds: float) -> None:
        """Account one answered batch: ``seconds`` is the executor's own
        time on it, dispatch plus result."""
        self.recorders[b].record(seconds, n)
        self._m_lat[b].observe(seconds)
        self._m_bat[b].inc()
        self._m_qry[b].inc(n)
        if b != first:
            self.fallbacks += 1
            self._m_fallback.labels(
                **{"from": first, "to": b, "shard": self._shard}).inc()

    def explain_batch(self, s: np.ndarray, t: np.ndarray,
                      mr_id: np.ndarray, n_real: Optional[int] = None,
                      backend: Optional[str] = None,
                      max_hubs: int = 8) -> Tuple[list, str]:
        """Witness mode of :meth:`execute`: per-query derivations instead
        of bare booleans; returns ``(witnesses[:n_real], backend)``.

        The backend is resolved with the same chain as ``execute`` so the
        witness reflects the layout the serving path would actually join
        — device backends explain over the padded/truncated device rows,
        ``numpy`` over the frozen CSR, ``python`` over the dict layout.
        """
        first = self.resolve(backend)
        n = len(s) if n_real is None else int(n_real)
        if first in ("pallas", "sorted") and self.device_index is not None:
            ws = self.device_index.explain_batch(s[:n], t[:n], mr_id[:n],
                                                 max_hubs=max_hubs)
            return ws, first
        if self.frozen is not None:
            ws = [self.frozen.explain(int(s[q]), int(t[q]),
                                      int(mr_id[q]), max_hubs=max_hubs)
                  for q in range(n)]
            return ws, "numpy"
        if self.id_to_mr is None:
            raise ExecutorError("no backend can explain this batch")
        ws = []
        for q in range(n):
            mr = self.id_to_mr[int(mr_id[q])]
            ws.append(self.index.explain(int(s[q]), int(t[q]), mr,
                                         mr_id=int(mr_id[q]),
                                         max_hubs=max_hubs))
        return ws, "python"

    @staticmethod
    def _pack_pow2(s, t, mr_id, n: int) -> np.ndarray:
        """The real slots of a batch as one ``(3, cap)`` int32 host array
        (rows ``s``, ``t``, ``mr``), padded to the next power of two by
        repeating slot 0 — batches arrive unpadded from the scheduler, and
        the jit backends need a bounded shape set ({1, 2, 4, ...}) to
        avoid re-tracing per fill level. Slot 0 is always a valid query;
        the caller slices answers back to ``n``. A fresh array per batch:
        the device may still read it after the call returns."""
        cap = 1
        while cap < n:
            cap <<= 1
        q = np.empty((3, cap), np.int32)
        for row, a in zip(q, (s, t, mr_id)):
            row[:n] = a[:n]
            row[n:] = a[0]
        return q

    def _launch(self, backend: str, s, t, mr_id, n: int, trace=None):
        """The device backends: the join's answers, still on the device,
        with their readback queued. The host backends: the answers."""
        # The device backends get pow2-padded shapes (static jit set);
        # the per-query loop backends run exactly the real slots.
        if backend in ("pallas", "sorted"):
            with self._ph_h2d(trace):
                q = self._pack_pow2(s, t, mr_id, n)
            with self._ph_dispatch(trace):
                # the jitted call transfers q itself: one host array
                out = self.device_index.join(q, backend)
                self._m_h2d[backend].inc()
                # queue the readback behind the join now, as a bare
                # np.asarray would: waiting first and only then asking
                # for the answers costs a second round trip to the device
                out.copy_to_host_async()
            return out
        if backend == "numpy":
            return self.frozen.query_batch(s[:n], t[:n], mr_id[:n])
        if backend == "python":
            out = np.zeros(n, dtype=bool)
            for q in range(n):
                out[q] = self.index.query(int(s[q]), int(t[q]),
                                          self.id_to_mr[int(mr_id[q])])
            return out
        raise ExecutorError(f"unknown backend {backend!r}")

    # ------------------------------------------------------------------ #
    def stats(self) -> Dict[str, Dict[str, float]]:
        """Summaries for every backend that actually served a batch."""
        return {b: r.summary() for b, r in self.recorders.items()
                if r.batches}


def _failed(backend: str, n: int) -> ExecutorError:
    return ExecutorError(f"backend {backend!r} failed on a batch of {n} "
                         "queries")


class PendingBatch:
    """A batch :meth:`BatchExecutor.dispatch` has started. On a device
    backend its answers may still be on the device; on a host backend
    they are already here."""

    __slots__ = ("_ex", "_first", "backend", "n", "_out", "_trace",
                 "_seconds", "_answers")

    def __init__(self, ex: BatchExecutor, first: str, backend: str, n: int,
                 out, trace, seconds: float):
        self._ex = ex
        self._first = first
        self.backend = backend
        self.n = n
        self._out = out
        self._trace = trace
        self._seconds = seconds          # the executor's time so far
        self._answers: Optional[np.ndarray] = None

    def done(self) -> bool:
        """True once the device has computed the answers: :meth:`result`
        then waits at most for their readback."""
        return (self._answers is not None
                or isinstance(self._out, np.ndarray)
                or self._out.is_ready())

    def result(self) -> Tuple[np.ndarray, str]:
        """``(answers[:n], backend)``, waiting for the device if need be
        (``exec.wait``, ``exec.d2h``). The first call accounts the batch
        as answered, with the time spent in dispatch and here; a failure
        raises :class:`ExecutorError` and accounts nothing."""
        if self._answers is None:
            ex, out, tr = self._ex, self._out, self._trace
            t0 = time.perf_counter()
            if not isinstance(out, np.ndarray):
                try:
                    with ex._ph_wait(tr):
                        out.block_until_ready()
                    with ex._ph_d2h(tr):
                        out = np.asarray(out)
                except Exception as e:
                    raise _failed(self.backend, self.n) from e
            self._out = None                 # drop the device buffer
            self._answers = np.asarray(out[:self.n], dtype=bool)
            ex._answered(self._first, self.backend, self.n,
                         self._seconds + time.perf_counter() - t0)
        return self._answers, self.backend
