"""Closed loop with writes: the calls of ``closed``, and between them the
traffic file's one-edge writes, each a ``GraphDelta`` through
``RLCService.apply_delta``.

The mix's ``writes_per_s`` puts ``n`` writes in a window of ``seconds``;
write ``j`` is applied at the first call boundary after ``(j + 1/2) /
writes_per_s`` into the window, and writes that run late follow one
another. The queries write ``j`` carries (those whose answer it changes,
and probes of the rows it most likely touches) go in one call of their
own just before it, so that their old answers sit in the result cache,
and again in the first call after it. The window ends one call after the
last write returns, or at ``seconds``, whichever is later.

Set-up makes the delta builder's first full build, so that no write in
the window pays it; the seconds spent drawing the writes and searching
the reference for their queries are kept out of ``setup_s``. The run
keeps each write's rows (``run.writes``), its time from the call until
the new device layout is ready (``run.write_s``), and for every answer
how many writes had returned before its call began (``run.epochs``):
the check compares it with the reference over the graph of that epoch.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench.lib import cell as cl
from bench.lib import traffic as tf
from bench.loops import closed


def prepare(run):
    from repro.core.graph import GraphDelta
    svc, cfg, mix = run.svc, run.cell.config, run.cell.traffic
    svc._ensure_delta_builder()
    calls = closed.prepare(run)
    t0 = time.perf_counter()
    writes = tf.run_writes(run.edges, cfg, mix, run.seed, run.pool.mrs,
                           tf.write_count(mix, run.seconds))
    run.reference_s += time.perf_counter() - t0
    n0 = len(run.pool)
    run.pool = run.pool.extended([q for w in writes
                                  for q in w.flips + w.probes])
    run.writes_due = len(writes)
    steps, flips, at = [], [], n0
    for w in writes:
        idx = np.arange(at, at + len(w.flips) + len(w.probes))
        flips.append(idx[:len(w.flips)])
        at += len(idx)
        steps.append((w.kind, GraphDelta.of(*w.rows()), idx,
                      run.pool.queries(idx)))
    run.flips = np.concatenate(flips)
    run.probes = np.setdiff1d(np.arange(n0, len(run.pool)), run.flips)
    return calls, steps, mix["writes_per_s"]


def window(run, state):
    (idx, calls), steps, rate = state
    svc, spans = run.svc, run.spans
    if spans:
        from jax.profiler import TraceAnnotation
    served, values, epochs = [], [], []
    run.calls, run.writes, run.write_s = [], [], []
    log = []

    def call(ix, qs):
        t = time.perf_counter()
        if spans:
            with TraceAnnotation("bench:call"):
                ans = svc.query_batch(qs)
        else:
            ans = svc.query_batch(qs)
        values.append([a.value for a in ans])
        served.append(ix)
        epochs.append(np.full(len(ix), len(run.writes)))
        run.calls.append((t, time.perf_counter()))

    n = 0
    t0 = time.perf_counter()
    due = [t0 + (j + 0.5) / rate for j in range(len(steps))]
    end = t0 + run.seconds
    after = None
    while True:
        j = len(run.writes)
        if j < len(steps) and time.perf_counter() >= due[j]:
            kind, delta, fx, fq = steps[j]
            call(fx, fq)
            t = time.perf_counter()
            if spans:
                with TraceAnnotation("bench:write"):
                    res = svc.apply_delta(delta)
                    cl.layout_ready(svc)
            else:
                res = svc.apply_delta(delta)
                cl.layout_ready(svc)
            run.write_s.append(time.perf_counter() - t)
            run.writes.append((delta.inserts, delta.deletes))
            log.append(f"{kind} {1e3 * run.write_s[-1]:.1f} ms "
                       f"(late {1e3 * (t - due[j]):.1f} ms, fallback "
                       f"{res['delta']['fallback_reason']}, row_len "
                       f"{svc.device_index.row_len})")
            after = fx, fq
        ix, qs = idx[n % len(calls)], calls[n % len(calls)]
        if after is not None:
            ix, qs = np.concatenate([ix, after[0]]), qs + after[1]
            after = None
        call(ix, qs)
        n += 1
        if run.calls[-1][1] >= end and len(run.writes) == len(steps):
            break
    run.window_s = run.calls[-1][1] - t0
    print(f"writes: {len(run.writes)} of {len(steps)}: " + "; ".join(log),
          file=sys.stderr, flush=True)
    good = closed.record(run, "stream loop", served, values)
    run.epochs = np.concatenate(epochs)[good]
