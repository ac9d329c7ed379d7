"""Phase spans (``Observability.phase``): one ``rlc_span_seconds``
observation per entry, inert when telemetry is off, the same span in a
sampled trace's buffer, and ``rlc:<phase>`` profiler annotations on the
host line that nest as the serving path does, with no per-query span on
the unsampled path."""
import glob
import os
import time

import numpy as np
import pytest

from repro.graphgen import erdos_renyi
from repro.obs import NULL_PHASE, Observability, Tracer, span_tree
from repro.service import RLCService, ServiceConfig

DEVICE_PHASES = ("exec.h2d", "exec.dispatch", "exec.wait", "exec.d2h")


def _span_cell(obs, name):
    return obs.registry.get("rlc_span_seconds").labels(span=name).reservoir


def test_phase_observes_once_per_entry():
    obs = Observability()
    ph = obs.phase("exec.wait")
    assert obs.phase("exec.wait") is ph       # one span per name
    for _ in range(3):
        with ph():
            pass
    with pytest.raises(RuntimeError):
        with ph():
            raise RuntimeError("x")
    res = _span_cell(obs, "exec.wait")
    assert res.count == 4 and res.total > 0.0
    # close() is idempotent: an entry ends once however often it closes
    ctx = ph().open()
    ctx.close()
    ctx.close()
    assert res.count == 5


def test_phase_is_inert_when_telemetry_is_off():
    obs = Observability(enabled=False)
    assert obs.phase("admit") is NULL_PHASE
    with obs.phase("admit")(Tracer(sample_rate=1.0).maybe_trace()):
        pass
    assert obs.registry.get("rlc_span_seconds") is None
    g = erdos_renyi(40, 2.5, 3, seed=5)
    svc = RLCService.build(g, ServiceConfig(
        k=2, use_device=False, backend="numpy", build_backend="numpy",
        telemetry=False))
    assert svc._ph_admit is NULL_PHASE and svc._ph_execute is NULL_PHASE
    assert svc.executor._ph_wait is NULL_PHASE
    mr = svc._id_to_mr[0]
    assert len(svc.query_batch([(0, 1, mr), (2, 3, mr)])) == 2


def test_phase_records_into_a_sampled_trace():
    obs = Observability(trace_sample_rate=1.0)
    tr = obs.tracer.maybe_trace()
    with obs.phase("execute", cat="service")(tr, n=3):
        with obs.phase("exec.h2d")(tr):
            pass
    with pytest.raises(ValueError):
        with obs.phase("answer")(tr):
            raise ValueError("x")
    with obs.phase("admit")():                 # unsampled entry
        pass
    names = {e.name: e for e in obs.tracer.events}
    assert set(names) == {"execute", "exec.h2d", "answer"}
    assert names["execute"].args == dict(n=3)
    assert names["execute"].cat == "service"
    assert names["answer"].args == dict(error="ValueError")
    (root,) = [r for r in span_tree(obs.tracer.events, tr.tid)
               if r.event.name == "execute"]
    assert [c.event.name for c in root.children] == ["exec.h2d"]


def _device_service(**kw):
    g = erdos_renyi(80, 2.5, 2, seed=3)
    # one MR and no deadline: batches flush full or at the drain only
    svc = RLCService.build(g, ServiceConfig(
        k=2, batch_size=8, max_wait_ms=1e6, backend="sorted",
        build_backend="numpy", **kw))
    for n in (1, 2, 4, 8):                     # compile outside the test
        z = np.zeros(n, np.int32)
        svc.executor.execute(z, z, z)
    rng = np.random.default_rng(0)
    mr = svc._id_to_mr[0]
    pairs = {(int(rng.integers(80)), int(rng.integers(80)))
             for _ in range(200)}
    return svc, [(s, t, mr) for s, t in sorted(pairs)[:44]]


def test_sampled_trace_keeps_execute_over_exec_backend():
    svc, queries = _device_service(trace_sample_rate=1.0)
    svc.query_batch(queries)
    doc = svc.chrome_trace()
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"execute", "resolve", "exec:sorted", "answer",
            "queue_wait"} <= names
    assert "admit[0]" in names
    (tid,) = {e.tid for e in svc.obs.tracer.events}
    roots = span_tree(svc.obs.tracer.events, tid)
    # each batch: its dispatch under `execute`, and later its readback and
    # answers under `resolve`
    execs = [r for r in roots if r.event.name == "execute"]
    assert len(execs) == 6                     # 5 full batches + drain
    for root in execs:
        assert [c.event.name for c in root.children] == ["exec:sorted"]
        assert [c.event.name for c in root.children[0].children] == \
            list(DEVICE_PHASES[:2])
    resolves = [r for r in roots if r.event.name == "resolve"]
    assert len(resolves) == 6
    for root in resolves:
        assert [c.event.name for c in root.children] == \
            list(DEVICE_PHASES[2:]) + ["answer"]


def _span_counts(svc):
    return {key[0]: cell.reservoir.count for key, cell in
            svc.obs.registry.get("rlc_span_seconds").series()}


def test_unsampled_path_adds_no_per_query_span():
    svc, queries = _device_service()
    before = _span_counts(svc)
    svc.query_batch(queries)
    got = {k: v - before.get(k, 0) for k, v in _span_counts(svc).items()}
    batches = -(-len(queries) // 8)
    full = len(queries) // 8
    assert got["query_batch"] == 1
    # one admission run before each mid-loop flush, and one after the last
    assert got["admit"] == full + 1
    # each batch opens each executor span once
    for name in ("execute", "resolve", "answer") + DEVICE_PHASES:
        assert got[name] == batches, name
    assert not svc.obs.tracer.events


def _rlc_events(tmp_path):
    import jax.profiler as jp
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    found = {}
    for plane in jp.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("rlc:", "test:")):
                    found.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return found


def _inside(child, parents):
    a, b = child
    return any(p0 <= a and b <= p1 for p0, p1 in parents)


def test_phases_land_on_the_profiler_host_line(tmp_path):
    import jax.profiler as jp
    svc, queries = _device_service()
    with jp.trace(str(tmp_path)):
        with jp.TraceAnnotation("test:outer"):
            svc.query_batch(queries)
    ev = _rlc_events(tmp_path)
    batches = -(-len(queries) // 8)
    assert len(ev["rlc:query_batch"]) == 1
    assert len(ev["rlc:admit"]) == len(queries) // 8 + 1
    for name in ("execute", "resolve", "answer") + DEVICE_PHASES:
        assert len(ev[f"rlc:{name}"]) == batches, name
    # the annotations nest as the serving path does, on one clock
    outer = ev["test:outer"]
    assert _inside(ev["rlc:query_batch"][0], outer)
    for name in ("rlc:admit", "rlc:execute", "rlc:resolve"):
        assert all(_inside(iv, ev["rlc:query_batch"]) for iv in ev[name])
    for name, parent in (("exec.h2d", "execute"), ("exec.dispatch", "execute"),
                         ("exec.wait", "resolve"), ("exec.d2h", "resolve"),
                         ("answer", "resolve")):
        assert all(_inside(iv, ev[f"rlc:{parent}"])
                   for iv in ev[f"rlc:{name}"]), name
    # admission runs overlap no dispatch and no readback
    for a in ev["rlc:admit"]:
        assert not any(a[0] < e1 and e0 < a[1]
                       for e0, e1 in ev["rlc:execute"] + ev["rlc:resolve"])
    # the six phases never overlap, so their shares add up to the part of
    # the window they cover
    six = sorted(iv for name in ("admit", "answer") + DEVICE_PHASES
                 for iv in ev[f"rlc:{name}"])
    assert all(a[1] <= b[0] for a, b in zip(six, six[1:]))


def test_ticker_tick_that_flushes_is_one_span():
    from repro.service.scheduler import MicroBatcher
    obs = Observability()
    b = MicroBatcher(batch_size=8, max_wait_s=0.02, obs=obs)
    flushed = []
    b.start_ticker(flushed.append, interval_s=0.001)
    try:
        b.submit(0, 1, 0, 1)
        b.submit(2, 3, 0, 2)
        deadline = time.monotonic() + 5.0
        while len(flushed) < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        time.sleep(0.05)                      # idle ticks after the flush
    finally:
        b.stop_ticker()
    assert len(flushed) == 2
    # idle ticks record nothing; a tick that flushes records once
    assert 1 <= _span_cell(obs, "tick").count <= 2
