"""Closed loop, one caller: ``RLCService.query_batch`` calls of
``call_size`` queries, back to back, until the window's time is up.

The pool is sent in one seeded permutation, cycled, so each query comes
back after exactly ``pool`` others: with a pool far above the result
cache's capacity, every query reaches the device join. ``warm_calls``
calls of another permutation run in set-up.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench.lib import traffic as tf


def _calls(pool, order, size):
    idx = [order[i:i + size] for i in range(0, len(order), size)]
    return idx, [pool.queries(ix) for ix in idx]


def prepare(run):
    pool, mix = run.pool, run.cell.traffic
    size = mix["call_size"]
    warm = tf.stream(run.seed, tf.WARM).permutation(len(pool))
    _, warm_calls = _calls(pool, warm, size)
    for qs in warm_calls[:mix["warm_calls"]]:
        run.svc.query_batch(qs)
    return _calls(pool, tf.stream(run.seed, tf.ORDER).permutation(len(pool)),
                  size)


def window(run, state):
    idx, calls = state
    svc, spans = run.svc, run.spans
    if spans:
        from jax.profiler import TraceAnnotation
    # values only (True, False, or None when shed): keeping the Answer
    # objects would grow the heap the collector walks inside the window
    served, values, ends = [], [], []
    n = 0
    t0 = time.perf_counter()
    end = t0 + run.seconds
    while True:
        qs = calls[n % len(calls)]
        if spans:
            with TraceAnnotation("bench:call"):
                ans = svc.query_batch(qs)
        else:
            ans = svc.query_batch(qs)
        values.append([a.value for a in ans])
        served.append(idx[n % len(calls)])
        n += 1
        ends.append(time.perf_counter())
        if ends[-1] >= end:
            break
    run.window_s = ends[-1] - t0
    run.calls = list(zip([t0] + ends[:-1], ends))
    record(run, "closed loop", served, values)


def record(run, loop: str, served, values) -> np.ndarray:
    """Keep the window's answers in ``run``: per call, the pool indices
    sent and the values that came back. Returns the mask of answered
    requests among those sent."""
    calls_ms = np.array([b - a for a, b in run.calls]) * 1e3
    print(f"{loop}: {len(calls_ms)} calls, call ms p50 "
          f"{np.median(calls_ms):.2f} max {calls_ms.max():.2f} "
          f"(call {int(calls_ms.argmax())})", file=sys.stderr, flush=True)
    flat = [v for vs in values for v in vs]
    good = np.array([v is not None for v in flat], bool)
    run.attempted = run.answered = len(flat)
    run.failed = int(len(flat) - good.sum())
    run.served = np.concatenate(served)[good]
    run.answers = np.array([v for v in flat if v is not None], bool)
    return good
