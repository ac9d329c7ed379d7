"""Telemetry contract tests (repro.obs): registry semantics, reservoir
percentile properties, the frozen ``repro.obs/1`` snapshot schema, span
tracing + Chrome export well-formedness, build/delta instrumentation,
and the end-to-end service integration (single-host and sharded,
including fallback attribution across replica hot-swaps)."""
import json
import os
import sys

import numpy as np
import pytest
from hypothesis import given
from hypothesis import strategies as st

from repro.build import (BuildStats, DeltaBuilder,
                         build_rlc_index_with_stats)
from repro.graphgen import erdos_renyi, random_delta
from repro.obs import (NULL_OBS, NULL_REGISTRY, SCHEMA, MetricsRegistry,
                       Observability, Reservoir, SpanEvent, Tracer,
                       snapshot, span_tree, to_prometheus,
                       validate_snapshot)
from repro.service import RLCService, ServiceConfig
from repro.service.metrics import LatencyRecorder
from repro.service.sharded import ShardedRLCService, ShardedServiceConfig


# ------------------------------------------------------------------ #
# Metrics registry
# ------------------------------------------------------------------ #
def test_registry_registration_idempotent():
    reg = MetricsRegistry()
    a = reg.counter("rlc_x", desc="first", labelnames=("backend",))
    b = reg.counter("rlc_x", desc="ignored", labelnames=("backend",))
    assert a is b
    assert reg.get("rlc_x") is a
    assert reg.get("nope") is None


def test_registry_conflicting_registration_raises():
    reg = MetricsRegistry()
    reg.counter("rlc_x", labelnames=("backend",))
    with pytest.raises(ValueError):
        reg.histogram("rlc_x", labelnames=("backend",))   # kind flip
    with pytest.raises(ValueError):
        reg.counter("rlc_x", labelnames=("shard",))       # label flip


def test_metric_labels_bind_cells():
    reg = MetricsRegistry()
    m = reg.counter("rlc_batches", labelnames=("backend",))
    cell = m.labels(backend="numpy")
    assert m.labels(backend="numpy") is cell        # get-or-create
    cell.inc()
    cell.inc(2.0)
    assert m.value(backend="numpy") == 3.0
    assert m.value(backend="pallas") == 0.0         # untouched series
    with pytest.raises(ValueError):
        m.labels(shard="0")                         # undeclared label
    with pytest.raises(ValueError):
        m.labels()                                  # missing label


def test_metric_conveniences_and_gauge():
    reg = MetricsRegistry()
    c = reg.counter("rlc_hits")
    c.inc()
    c.inc(4)
    assert c.value() == 5.0
    g = reg.gauge("rlc_size")
    g.set(7)
    assert g.value() == 7.0
    h = reg.histogram("rlc_lat", labelnames=("backend",))
    h.observe(0.5, backend="numpy")
    ((key, cell),) = h.series()
    assert key == ("numpy",)
    assert cell.reservoir.count == 1


def test_null_registry_records_nothing():
    m = NULL_REGISTRY.counter("rlc_x", labelnames=("a",))
    m.labels(a="1").inc()
    m.inc(5, a="2")
    assert NULL_REGISTRY.get("rlc_x") is None
    assert NULL_REGISTRY.as_dict() == {}
    assert list(m.series()) == []


# ------------------------------------------------------------------ #
# Reservoir
# ------------------------------------------------------------------ #
def test_reservoir_exact_below_cap():
    r = Reservoir(cap=256)
    rng = np.random.default_rng(3)
    xs = rng.normal(size=200)
    for x in xs:
        r.add(x)
    assert r.exact
    for p in (0, 25, 50, 90, 99, 100):
        assert r.percentile(p) == pytest.approx(
            float(np.percentile(xs, p)), abs=1e-12)


def test_reservoir_bounded_above_cap():
    r = Reservoir(cap=64)
    n = 64 * 20
    for i in range(n):
        r.add(float(i))
    assert len(r.samples) == 64                 # bounded memory
    assert not r.exact
    assert r.count == n                         # exact aggregates forever
    assert r.total == pytest.approx(sum(range(n)))
    assert r.vmin == 0.0 and r.vmax == float(n - 1)
    # the reservoir is a uniform subset, so the median estimate must land
    # well inside the value range (Algorithm R, deterministic seed)
    assert 0.2 * n < r.percentile(50) < 0.8 * n


def test_reservoir_keeps_a_uniform_sample():
    # every observation is stored with probability cap / n, the first
    # ones (the filled reservoir) as the last ones (the skips drawn)
    n, cap, runs = 200, 10, 2000
    kept = np.zeros(n)
    for seed in range(runs):
        r = Reservoir(cap=cap, seed=seed)
        for i in range(n):
            r.add(float(i))
        assert len(r.samples) == cap
        kept[np.asarray(r.samples, int)] += 1
    share = kept / runs
    for part in np.split(share, 10):
        assert part.mean() == pytest.approx(cap / n, abs=0.005)


def test_reservoir_summary_keys_frozen():
    r = Reservoir(cap=8)
    assert set(r.summary()) == {"count", "sum", "min", "max", "p50", "p90",
                                "p99", "stored", "exact"}
    assert r.summary()["count"] == 0
    assert r.summary()["min"] == 0.0            # empty-summary convention


@given(st.lists(st.integers(min_value=-1000, max_value=1000),
                min_size=1, max_size=100))
def test_reservoir_percentiles_match_numpy_below_cap(xs):
    r = Reservoir(cap=128)
    for x in xs:
        r.add(float(x))
    for p in (10, 50, 99):
        assert r.percentile(p) == pytest.approx(
            float(np.percentile(np.asarray(xs, float), p)), abs=1e-9)


def test_latency_recorder_bounded_with_stable_summary():
    rec = LatencyRecorder("numpy", sample_cap=32)
    for i in range(1000):
        rec.record(0.001 * (i % 10 + 1), n_queries=4)
    assert len(rec.samples_s) == 32             # the old list grew forever
    assert rec.batches == 1000 and rec.queries == 4000
    s = rec.summary()
    assert set(s) == {"batches", "queries", "total_s", "p50_ms", "p99_ms",
                      "qps"}
    assert s["qps"] == pytest.approx(4000 / rec.total_s)


# ------------------------------------------------------------------ #
# Tracing
# ------------------------------------------------------------------ #
def test_tracer_sampling_rates():
    assert Tracer(sample_rate=0.0).maybe_trace() is None
    t = Tracer(sample_rate=1.0)
    assert t.maybe_trace() is not None
    half = Tracer(sample_rate=0.5)
    got = sum(half.maybe_trace() is not None for _ in range(1000))
    assert half.traces_started + half.traces_skipped == 1000
    assert 350 < got < 650                      # seeded, loose band
    with pytest.raises(ValueError):
        Tracer(sample_rate=1.5)


def test_trace_span_records_and_propagates_errors():
    tracer = Tracer(sample_rate=1.0)
    tr = tracer.maybe_trace()
    with tr.span("outer", cat="service", n=3):
        with tr.span("inner"):
            pass
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    names = {e.name: e for e in tracer.events}
    assert set(names) == {"outer", "inner", "boom"}
    assert names["outer"].args == dict(n=3)
    assert names["boom"].args["error"] == "RuntimeError"
    # nesting by interval containment: inner sits inside outer
    roots = span_tree(tracer.events, tr.tid)
    by_name = {r.event.name: r for r in roots}
    assert by_name["outer"].children[0].event.name == "inner"
    assert not by_name["boom"].children


def test_trace_add_ending_now_backdates():
    tracer = Tracer(sample_rate=1.0)
    tr = tracer.maybe_trace()
    tr.add_ending_now("queue_wait", 0.25, cat="batcher")
    (ev,) = tracer.events
    assert ev.dur == pytest.approx(0.25)
    assert ev.ts + ev.dur == pytest.approx(tracer._now(), abs=0.05)


def test_tracer_event_buffer_bounded():
    tracer = Tracer(sample_rate=1.0, max_events=10)
    tr = tracer.maybe_trace()
    for i in range(25):
        tr.add(f"s{i}", 0.0, 0.001)
    assert len(tracer.events) == 10
    assert tracer.dropped == 15
    assert tracer.stats()["dropped"] == 15
    tracer.clear()
    assert not tracer.events and tracer.dropped == 0


def test_span_tree_partial_overlap_stays_top_level():
    a = SpanEvent("a", "", 1, ts=0.0, dur=1.0)
    b = SpanEvent("b", "", 1, ts=0.5, dur=1.0)     # overlaps, not nested
    roots = span_tree([a, b], tid=1)
    assert [r.event.name for r in roots] == ["a", "b"]


def test_chrome_trace_export_shape():
    tracer = Tracer(sample_rate=1.0)
    tr = tracer.maybe_trace()
    with tr.span("execute", cat="service"):
        pass
    doc = tracer.chrome_trace("unit-test")
    evs = doc["traceEvents"]
    assert evs[0]["ph"] == "M"
    assert evs[0]["args"]["name"] == "unit-test"
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == 1
    assert xs[0]["name"] == "execute"
    assert xs[0]["ts"] >= 0 and xs[0]["dur"] >= 0   # microseconds
    json.dumps(doc)                                 # serializable as-is


# ------------------------------------------------------------------ #
# Snapshot schema (frozen contract) + Prometheus export
# ------------------------------------------------------------------ #
def _populated_registry():
    reg = MetricsRegistry(reservoir_cap=16)
    reg.counter("rlc_cache_lookups", desc="lookups",
                labelnames=("outcome",)).inc(3, outcome="hit")
    reg.gauge("rlc_cache_size").set(2)
    h = reg.histogram("rlc_executor_batch_seconds", unit="s",
                      labelnames=("backend", "shard"))
    for v in (0.001, 0.002, 0.004):
        h.observe(v, backend="numpy", shard="-")
    return reg


def test_schema_version_is_frozen():
    # bump the version string when the shape changes — consumers (CI
    # smoke validation, bench artifacts) key on it
    assert SCHEMA == "repro.obs/1"


def test_snapshot_validates_and_serializes():
    reg = _populated_registry()
    tracer = Tracer(sample_rate=1.0)
    with tracer.maybe_trace().span("x"):
        pass
    doc = snapshot(reg, tracer=tracer, extra=dict(queries_served=3))
    assert validate_snapshot(doc) is doc
    doc2 = json.loads(json.dumps(doc))          # survives a JSON round-trip
    validate_snapshot(doc2)
    assert doc2["schema"] == SCHEMA
    assert doc2["extra"] == dict(queries_served=3)
    hist = doc2["metrics"]["rlc_executor_batch_seconds"]
    assert hist["series"][0]["labels"] == dict(backend="numpy", shard="-")
    assert hist["series"][0]["count"] == 3


@pytest.mark.parametrize("mutate, path_hint", [
    (lambda d: d.update(schema="repro.obs/0"), "schema"),
    (lambda d: d["metrics"]["rlc_cache_size"].update(type="blob"), "type"),
    (lambda d: d["metrics"]["rlc_executor_batch_seconds"]["series"][0]
        .pop("p99"), "missing"),
    (lambda d: d["metrics"]["rlc_executor_batch_seconds"]["series"][0]
        .update(stored=99), "stored"),
    (lambda d: d["metrics"]["rlc_cache_lookups"]["series"][0]
        .update(labels={}), "labels"),
    (lambda d: d["metrics"]["rlc_cache_size"]["series"][0]
        .update(value="two"), "value"),
    (lambda d: d.update(tracing=dict(sample_rate="high")), "tracing"),
    # a reservoir that observed anything keeps >= 1 sample: count>0 with
    # stored==0 means the series was assembled by hand or clobbered
    (lambda d: d["metrics"]["rlc_executor_batch_seconds"]["series"][0]
        .update(stored=0), "stored"),
    # NaN/inf percentiles serialize to invalid JSON and poison
    # aggregation downstream — the validator must reject, not pass,
    # non-finite floats
    (lambda d: d["metrics"]["rlc_executor_batch_seconds"]["series"][0]
        .update(p50=float("nan")), "p50"),
    (lambda d: d["metrics"]["rlc_executor_batch_seconds"]["series"][0]
        .update(sum=float("inf")), "sum"),
    (lambda d: d["metrics"]["rlc_cache_size"]["series"][0]
        .update(value=float("nan")), "value"),
    (lambda d: d["metrics"]["rlc_executor_batch_seconds"]["series"][0]
        .update(count=True), "count"),
])
def test_snapshot_rejects_malformed(mutate, path_hint):
    doc = snapshot(_populated_registry())
    mutate(doc)
    with pytest.raises(ValueError, match=path_hint):
        validate_snapshot(doc)


def test_prometheus_text_format():
    text = to_prometheus(_populated_registry())
    lines = text.splitlines()
    # counters get _total; histograms export as summaries
    assert 'rlc_cache_lookups_total{outcome="hit"} 3' in lines
    assert "# TYPE rlc_cache_lookups_total counter" in lines
    assert "# TYPE rlc_executor_batch_seconds summary" in lines
    assert ('rlc_executor_batch_seconds{backend="numpy",quantile="0.5",'
            'shard="-"} 0.002') in lines
    assert 'rlc_executor_batch_seconds_count{backend="numpy",shard="-"} 3' \
        in lines
    assert "rlc_cache_size 2" in lines


def test_prometheus_escapes_label_values():
    reg = MetricsRegistry()
    reg.counter("rlc_x", labelnames=("q",)).inc(1, q='say "hi" \\ there')
    text = to_prometheus(reg)
    assert r'{q="say \"hi\" \\ there"}' in text


# ------------------------------------------------------------------ #
# Observability facade
# ------------------------------------------------------------------ #
def test_observability_disabled_is_inert():
    obs = Observability(enabled=False)
    assert obs.registry is NULL_REGISTRY
    assert obs.tracer.maybe_trace() is None
    assert obs.build_observer() is None
    doc = validate_snapshot(obs.snapshot())
    assert doc["metrics"] == {}
    assert NULL_OBS.registry is NULL_REGISTRY


def test_observability_build_observer_contexts():
    obs = Observability()
    assert obs.build_observer() is obs.build_observer()     # "full" cached
    assert obs.build_observer("delta") is not obs.build_observer("delta")


# ------------------------------------------------------------------ #
# Build / delta instrumentation
# ------------------------------------------------------------------ #
def test_build_phase_observer_accounts_every_phase():
    g = erdos_renyi(80, 2.5, 3, seed=7)
    obs = Observability()
    index, stats = build_rlc_index_with_stats(
        g, 2, backend="numpy", observer=obs.build_observer())
    reg = obs.registry
    phases = reg.get("rlc_build_phases")
    n_phases = sum(c.value for _k, c in phases.series())
    assert 0 < n_phases <= 2 * g.num_vertices    # one per (hub, direction)
    # the per-phase counter deltas must sum back to the build totals
    deltas = reg.get("rlc_build_counter_deltas")
    for name, total in zip(BuildStats._COUNTERS, stats.counters()):
        assert deltas.value(context="full", counter=name) == total
    assert reg.get("rlc_build_runs").value(
        context="full", backend="numpy") == 1
    slowest = obs.build_observer().slowest_phases()
    assert slowest and slowest == sorted(
        slowest, key=lambda p: -p["seconds"])
    assert {"hub", "direction", "seconds"} <= set(slowest[0])
    # and the facade snapshot carries them in extra
    doc = validate_snapshot(obs.snapshot())
    assert doc["extra"]["slowest_build_phases"] == slowest


def test_delta_builder_reports_outcomes_and_fallback_reason():
    g = erdos_renyi(100, 2.2, 3, seed=11)
    obs = Observability()
    db = DeltaBuilder(g, 2, backend="numpy", fallback_frac=1.0, obs=obs)
    db.full()
    rng = np.random.default_rng(2)
    res = db.apply(random_delta(db.graph, 2, 2, rng))
    assert res.fallback_reason is None
    reg = obs.registry
    assert reg.get("rlc_delta_applies").value(outcome="incremental") == 1
    assert reg.get("rlc_delta_apply_seconds").labels().reservoir.count == 1
    # delta-context phases land labeled apart from full-build phases
    phase_ctx = {k[0] for k, _c in
                 reg.get("rlc_build_phases").series()}
    assert "delta_full" in phase_ctx            # the traced bootstrap
    # a second builder with a zero-work budget must bail to the rebuild
    # path and attribute why
    db2 = DeltaBuilder(g, 2, backend="numpy", fallback_frac=1e-9, obs=obs)
    db2.full()
    res2 = db2.apply(random_delta(db2.graph, 2, 2, rng))
    assert res2.fallback
    assert res2.fallback_reason in ("static_budget", "budget")
    assert reg.get("rlc_delta_fallbacks").value(
        reason=res2.fallback_reason) == 1
    assert reg.get("rlc_delta_applies").value(outcome="fallback") == 1


# ------------------------------------------------------------------ #
# Service integration (single-host)
# ------------------------------------------------------------------ #
def _service_queries(svc, n=40, seed=0):
    rng = np.random.default_rng(seed)
    V = svc.graph.num_vertices
    mrs = list(svc._id_to_mr)
    return [(int(rng.integers(V)), int(rng.integers(V)),
             mrs[int(rng.integers(len(mrs)))]) for _ in range(n)]


def test_service_telemetry_end_to_end():
    g = erdos_renyi(90, 2.5, 3, seed=13)
    svc = RLCService.build(g, ServiceConfig(
        k=2, batch_size=8, use_device=False, backend="numpy",
        build_backend="numpy", trace_sample_rate=1.0))
    queries = _service_queries(svc, n=40)
    svc.query_batch(queries)
    svc.query_batch(queries)        # second pass hits the result cache
    reg = svc.obs.registry
    # every admitted (non-cached) request got a queue-wait observation
    wait = sum(c.reservoir.count for _k, c in
               reg.get("rlc_batcher_queue_wait_seconds").series())
    st = svc.stats()
    assert wait == st["cache"]["misses"]
    assert reg.get("rlc_cache_lookups").value(outcome="hit") == \
        st["cache"]["hits"] > 0
    assert reg.get("rlc_executor_queries").value(
        backend="numpy", shard="-") == wait
    # sampled traces: every query_batch call traced at rate 1.0
    ts = svc.obs.tracer.stats()
    assert ts["traces"] == 2 and ts["events"] > 0
    # span tree: the execute span nests its executor attempt
    tids = {e.tid for e in svc.obs.tracer.events}
    execs = 0
    for tid in tids:
        for root in span_tree(svc.obs.tracer.events, tid):
            if root.event.name == "execute":
                assert any(c.event.name.startswith("exec:")
                           for c in root.children)
                execs += 1
    assert execs > 0
    # exporters: snapshot validates + prom text + chrome trace
    doc = validate_snapshot(svc.telemetry_snapshot())
    assert doc["extra"]["queries_served"] == 80
    assert "rlc_batcher_queue_wait_seconds" in svc.prometheus()
    trace = svc.chrome_trace()
    assert any(e["ph"] == "X" and e["name"] == "queue_wait"
               for e in trace["traceEvents"])
    assert st["telemetry"]["enabled"]
    assert st["telemetry"]["tracing"]["traces"] == 2


def test_service_telemetry_disabled_still_serves():
    g = erdos_renyi(60, 2.0, 3, seed=13)
    cfg_on = ServiceConfig(k=2, batch_size=8, use_device=False,
                           backend="numpy", build_backend="numpy")
    svc_on = RLCService.build(g, cfg_on)
    svc_off = RLCService.build(
        g, ServiceConfig(k=2, batch_size=8, use_device=False,
                         backend="numpy", build_backend="numpy",
                         telemetry=False), index=svc_on.index)
    queries = _service_queries(svc_on, n=30, seed=4)
    assert svc_off.query_batch(queries) == svc_on.query_batch(queries)
    assert not svc_off.stats()["telemetry"]["enabled"]
    doc = validate_snapshot(svc_off.telemetry_snapshot())
    assert doc["metrics"] == {}


# ------------------------------------------------------------------ #
# Sharded integration: fallback attribution across hot-swaps
# ------------------------------------------------------------------ #
def test_sharded_fallbacks_survive_hot_swap():
    g = erdos_renyi(90, 2.5, 3, seed=17)
    # pallas without a device layout can never serve: every batch falls
    # back pallas -> numpy, making fallback attribution deterministic
    svc = ShardedRLCService.build(g, ShardedServiceConfig(
        k=2, batch_size=8, num_shards=2, use_device=False,
        backend="pallas", build_backend="numpy"))
    queries = _service_queries(svc, n=40, seed=5)
    svc.query_batch(queries)
    before = [sh.fallbacks for sh in svc.shards]
    assert sum(before) > 0
    reg = svc.obs.registry
    fb = reg.get("rlc_executor_fallbacks")
    assert sum(c.value for _k, c in fb.series()) == sum(before)
    svc.hot_swap()                  # rebuild + atomic republish per shard
    svc.query_batch(_service_queries(svc, n=40, seed=6))
    after = [sh.fallbacks for sh in svc.shards]
    # new executors start at zero — the banked counts keep attribution
    # monotone across the generation, per shard
    assert all(a >= b for a, b in zip(after, before))
    assert sum(after) > sum(before)
    for sh, a in zip(svc.shards, after):
        assert sh.stats()["fallbacks"] == a
        assert fb.value(**{"from": "pallas", "to": "numpy",
                           "shard": str(sh.shard_id)}) == a
    # and the shard-labeled registry series agree with the banked totals
    totals = svc.shards[0].backend_totals()
    assert totals["numpy"]["batches"] > 0
    doc = validate_snapshot(svc.telemetry_snapshot())
    assert "rlc_router_routes" in doc["metrics"]


# ------------------------------------------------------------------ #
# Benchmark-side validation (the CI smoke gate)
# ------------------------------------------------------------------ #
def test_run_py_validates_telemetry_artifacts(tmp_path, monkeypatch):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks import run as bench_run
    monkeypatch.setattr(bench_run, "ART", str(tmp_path))
    good = snapshot(_populated_registry())
    trace = dict(traceEvents=[
        dict(name="process_name", ph="M", pid=0, tid=0, args={}),
        dict(name="execute", ph="X", pid=0, tid=1, ts=1.0, dur=2.0)])

    def write(name, doc):
        with open(tmp_path / name, "w") as f:
            json.dump(doc, f)

    # a real audit report + clean shadow stats, as the serving suites
    # embed them via telemetry_snapshot's extra section
    svc = RLCService.build(erdos_renyi(40, 2.5, 3, seed=3),
                           ServiceConfig(k=2, use_device=False))
    audit = svc.audit_report(sample=16)
    good["extra"] = dict(audit=audit, shadow=dict(divergent=0, checked=4))

    # minimal rpc-transport stats doc satisfying run.py's rpc_stage_ok:
    # a real in-proc sharded stats document with the rpc section the
    # wire transport would add
    sh = ShardedRLCService.build(
        erdos_renyi(40, 2.5, 3, seed=3),
        ShardedServiceConfig(k=2, num_shards=2, use_device=False))
    rpc_stats = sh.stats()
    rpc_stats["transport"] = "rpc"
    rpc_stats["rpc"] = dict(
        live_workers=2, membership_epoch=1, joins=2, leaves=0,
        rejoins=0, retries=0, generation=0,
        wire_bytes=dict(sent=1000, received=500))
    # minimal control-plane + rpc stages satisfying run.py's
    # control_stages_ok / rpc_stage_ok / stats_schema_ok
    control = dict(
        slo=dict(shed=0, p99_over_p50=1.5),
        overload=dict(shed_ratio=0.1, underload_shed=0,
                      answers_match_oracle=True,
                      underload=dict(answers_match_oracle=True)),
        warming=dict(cold_hit_rate=0.3, warm_hit_rate=0.6),
        rpc=dict(shards=2, answers_match=True, digest_wire_kb=0.5,
                 roundtrips=7, stats=rpc_stats),
        rpc_async=dict(answers_match=True, overlap_s=0.01))
    write("service.json", dict(results=dict(numpy=dict(telemetry=good))))
    write("sharded.json", dict(results=dict(
        shards_2=dict(telemetry=good), **control)))
    write("sharded_trace.json", trace)
    assert bench_run.validate_telemetry_artifacts(["service",
                                                   "sharded"]) == []
    # a control-plane invariant violation must fail the smoke run
    broken = dict(control, slo=dict(shed=3, p99_over_p50=1.5))
    write("sharded.json", dict(results=dict(
        shards_2=dict(telemetry=good), **broken)))
    fails = bench_run.validate_telemetry_artifacts(["sharded"])
    assert any(name == "sharded:control" for name, _err in fails)
    write("sharded.json", dict(results=dict(
        shards_2=dict(telemetry=good), **control)))
    # a snapshot that stops validating must fail the smoke run
    bad = json.loads(json.dumps(good))
    bad["schema"] = "repro.obs/999"
    write("service.json", dict(results=dict(numpy=dict(telemetry=bad))))
    fails = bench_run.validate_telemetry_artifacts(["service"])
    # the audit walker skips unrecognized schemas, so both checks trip
    assert [name for name, _err in fails] == ["service:telemetry",
                                              "service:audit"]
    # a shadow divergence recorded in any embedded snapshot fails the run
    diverged = json.loads(json.dumps(good))
    diverged["extra"]["shadow"]["divergent"] = 1
    write("service.json",
          dict(results=dict(numpy=dict(telemetry=diverged))))
    fails = bench_run.validate_telemetry_artifacts(["service"])
    assert any(name == "service:audit" for name, _err in fails)
    # a corrupted audit report fails the run too
    bad_audit = json.loads(json.dumps(good))
    bad_audit["extra"]["audit"]["identity"]["entries"] += 1
    write("service.json",
          dict(results=dict(numpy=dict(telemetry=bad_audit))))
    fails = bench_run.validate_telemetry_artifacts(["service"])
    assert any(name == "service:audit" for name, _err in fails)
    # suites with no embedded telemetry at all must also fail
    write("sharded.json", dict(results=dict(shards_2=dict(qps=1.0))))
    fails = bench_run.validate_telemetry_artifacts(["sharded"])
    assert any(name == "sharded:telemetry" for name, _err in fails)
