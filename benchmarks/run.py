"""Benchmark orchestrator — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--smoke] [--only NAME]

(also runnable as ``python benchmarks/run.py``: the shim below puts the
repo root and ``src/`` on ``sys.path`` — what the CI smoke job invokes.)

Default is quick mode (scaled-down graphs, single-core container);
``--full`` runs paper-scale sweeps; ``--smoke`` runs every registered
suite at tiny sizes — it exists to fail on crash and keep per-PR JSON
artifacts flowing, not to produce meaningful numbers. CSVs (and the
JSON artifacts some suites emit) land in benchmarks/artifacts/.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

if __package__ in (None, ""):                     # script execution
    _HERE = os.path.dirname(os.path.abspath(__file__))
    _ROOT = os.path.dirname(_HERE)
    for p in (_ROOT, os.path.join(_ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    __package__ = "benchmarks"

ART = os.path.join(os.path.dirname(os.path.abspath(__file__)), "artifacts")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes, every suite; fails on crash")
    ap.add_argument("--only", type=str, default=None)
    args = ap.parse_args(argv)
    if args.full and args.smoke:
        ap.error("--full and --smoke are mutually exclusive")
    quick = not args.full
    smoke = args.smoke
    os.makedirs(ART, exist_ok=True)
    from repro.device import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    from . import (bench_delta, bench_device, bench_graph_chars,
                   bench_indexing, bench_k, bench_query, bench_scalability,
                   bench_service, bench_sharded, bench_systems)

    suites = {
        "indexing": lambda: bench_indexing.run(quick, smoke),
        "build_backends": lambda: bench_indexing.run_backends(quick, smoke),
        "pruning": lambda: bench_indexing.run_pruning_ablation(smoke),
        "delta": lambda: bench_delta.run(quick, smoke),
        "query": lambda: bench_query.run(quick, smoke),
        "k": lambda: bench_k.run(quick, smoke),
        "graph_chars": lambda: bench_graph_chars.run(quick, smoke),
        "scalability": lambda: bench_scalability.run(quick, smoke),
        "systems": lambda: bench_systems.run(quick, smoke),
        "device": lambda: bench_device.run(quick, smoke),
        "service": lambda: bench_service.run(quick, smoke),
        "sharded": lambda: bench_sharded.run(quick, smoke),
    }
    failures = []
    ran = []
    for name, fn in suites.items():
        if args.only and args.only != name:
            continue
        ran.append(name)
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        try:
            rep = fn()
            csv = rep.to_csv()
            with open(os.path.join(ART, f"{rep.name}.csv"), "w") as f:
                f.write(csv)
            print(f"===== {name} done in {time.time()-t0:.1f}s "
                  f"({len(rep.rows)} rows) =====", flush=True)
        except Exception as e:  # pragma: no cover
            import traceback
            traceback.print_exc()
            failures.append((name, repr(e)))
    failures.extend(validate_telemetry_artifacts(ran))
    if smoke and not args.only:
        from .regression import gate
        failures.extend(gate(ART))
    if failures:
        print("\nFAILED suites:", failures)
        sys.exit(1)
    print("\nAll benchmark suites completed.")


def validate_telemetry_artifacts(ran):
    """Check the telemetry the serving suites just emitted: every snapshot
    embedded in their JSON artifacts must parse against the versioned
    schema, the Chrome trace dump must be well-formed, every embedded
    index-health audit report must validate (and is consolidated into
    ``artifacts/audit.json``), and the shadow verifier must report zero
    divergences. Runs only for the suites that actually executed; returns
    ``(name, error)`` failure tuples in the orchestrator's format."""
    import json

    from repro.obs import validate_audit_report, validate_snapshot

    failures = []

    def check(name, fn):
        try:
            fn()
        except Exception as e:
            failures.append((name, repr(e)))

    def snapshots_of(path):
        with open(path) as f:
            doc = json.load(f)
        found = 0
        for res in doc.get("results", {}).values():
            if isinstance(res, dict) and "telemetry" in res:
                validate_snapshot(res["telemetry"])
                found += 1
        if "telemetry" in doc.get("results", {}):
            validate_snapshot(doc["results"]["telemetry"])
        if not found:
            raise ValueError(f"no telemetry snapshots in {path}")

    def chrome_trace_ok(path):
        with open(path) as f:
            doc = json.load(f)
        evs = doc["traceEvents"]
        if not isinstance(evs, list) or not evs:
            raise ValueError("empty traceEvents")
        for ev in evs:
            if ev["ph"] not in ("X", "M"):
                raise ValueError(f"unexpected phase {ev['ph']!r}")
            if ev["ph"] == "X" and (ev["dur"] < 0 or ev["ts"] < 0):
                raise ValueError(f"negative ts/dur in {ev}")

    def control_stages_ok(path):
        """The adaptive-serving stages must have run and their invariants
        must hold: no shedding at/below capacity, shedding engaged (and
        every non-shed answer oracle-identical) at 2x capacity, and the
        warmed post-swap hit rate at least matching the cold one."""
        with open(path) as f:
            doc = json.load(f)
        res = doc.get("results", {})
        for key in ("slo", "overload", "warming"):
            if key not in res:
                raise ValueError(f"no {key!r} stage in {path}")
        slo = res["slo"]
        if slo["shed"] != 0:
            raise ValueError(f"slo stage shed {slo['shed']} queries at "
                             f"offered load <= capacity")
        ov = res["overload"]
        if ov["underload_shed"] != 0:
            raise ValueError(f"shed {ov['underload_shed']} queries at "
                             f"0.5x capacity")
        if not ov["answers_match_oracle"] or not ov["underload"][
                "answers_match_oracle"]:
            raise ValueError("non-shed answers diverged from the "
                             "single-host oracle under overload")
        if not isinstance(ov["shed_ratio"], (int, float)):
            raise ValueError(f"bad overload shed_ratio {ov['shed_ratio']!r}")
        wm = res["warming"]
        if wm["warm_hit_rate"] < wm["cold_hit_rate"]:
            raise ValueError(
                f"warming hurt the post-swap hit rate: warmed "
                f"{wm['warm_hit_rate']} < cold {wm['cold_hit_rate']}")

    def rpc_stage_ok(path):
        """The multi-process RPC stages must have run, answered
        bit-identically to the single-process oracle, actually moved
        digest bytes over the wire, demonstrated admission/execution
        overlap, and embedded a valid ``repro.service.stats/1`` doc."""
        from repro.service import validate_stats
        with open(path) as f:
            doc = json.load(f)
        res = doc.get("results", {})
        for key in ("rpc", "rpc_async"):
            if key not in res:
                raise ValueError(f"no {key!r} stage in {path}")
        rpc = res["rpc"]
        if not rpc["answers_match"]:
            raise ValueError("rpc answers diverged from the "
                             "single-process oracle")
        if rpc["shards"] > 1 and rpc["digest_wire_kb"] <= 0:
            raise ValueError("multi-shard rpc run shipped no digest "
                             "bytes over the wire")
        if rpc["roundtrips"] <= 0:
            raise ValueError("no rpc round-trips recorded")
        stats = rpc.get("stats")
        validate_stats(stats)
        if stats.get("transport") != "rpc":
            raise ValueError(
                f"expected transport 'rpc' in embedded stats, "
                f"got {stats.get('transport')!r}")
        a = res["rpc_async"]
        if not a["answers_match"]:
            raise ValueError("async rpc answers diverged from the "
                             "single-process oracle")
        if not a["overlap_s"] > 0:
            raise ValueError(
                f"submit() showed no admission/execution overlap "
                f"(overlap_s={a['overlap_s']!r})")

    def stats_schema_ok(path):
        """Every service stats document a suite embedded must validate
        against the versioned ``repro.service.stats/1`` schema."""
        from repro.service import validate_stats
        with open(path) as f:
            doc = json.load(f)
        found = 0
        for res in doc.get("results", {}).values():
            if isinstance(res, dict) and isinstance(res.get("stats"),
                                                    dict) \
                    and "schema" in res["stats"]:
                validate_stats(res["stats"])
                found += 1
        if not found:
            raise ValueError(f"no versioned stats documents in {path}")

    def parallel_speedup_ok(path):
        with open(path) as f:
            doc = json.load(f)
        sp = doc.get("parallel_speedup")
        if not isinstance(sp, (int, float)) or sp <= 0:
            raise ValueError(
                f"missing/invalid parallel_speedup in {path}: {sp!r}")
        if not doc.get("parallel", {}).get("rows"):
            raise ValueError(f"no parallel scaling rows in {path}")

    audits = {}

    def _walk_extras(doc):
        """Every snapshot ``extra`` section embedded in a bench JSON."""
        if isinstance(doc, dict):
            if doc.get("schema") == "repro.obs/1" and "extra" in doc:
                yield doc["extra"]
            else:
                for v in doc.values():
                    yield from _walk_extras(v)
        elif isinstance(doc, list):
            for v in doc:
                yield from _walk_extras(v)

    def audits_and_shadow_of(name, path):
        with open(path) as f:
            doc = json.load(f)
        found = []
        for extra in _walk_extras(doc):
            audit = extra.get("audit")
            if audit is not None:
                validate_audit_report(audit)
                found.append(audit)
            shadow = extra.get("shadow")
            if shadow is not None and shadow.get("divergent", 0) != 0:
                raise ValueError(
                    f"shadow verifier diverged in {path}: {shadow}")
        if not found:
            raise ValueError(f"no audit reports embedded in {path}")
        audits[name] = found

    if "build_backends" in ran:
        check("build_backends:parallel_speedup", lambda: parallel_speedup_ok(
            os.path.join(ART, "indexing.json")))
    if "service" in ran:
        check("service:telemetry",
              lambda: snapshots_of(os.path.join(ART, "service.json")))
        check("service:audit", lambda: audits_and_shadow_of(
            "service", os.path.join(ART, "service.json")))
    if "sharded" in ran:
        check("sharded:telemetry",
              lambda: snapshots_of(os.path.join(ART, "sharded.json")))
        check("sharded:trace", lambda: chrome_trace_ok(
            os.path.join(ART, "sharded_trace.json")))
        check("sharded:audit", lambda: audits_and_shadow_of(
            "sharded", os.path.join(ART, "sharded.json")))
        check("sharded:control", lambda: control_stages_ok(
            os.path.join(ART, "sharded.json")))
        check("sharded:rpc", lambda: rpc_stage_ok(
            os.path.join(ART, "sharded.json")))
        check("sharded:stats_schema", lambda: stats_schema_ok(
            os.path.join(ART, "sharded.json")))
    if audits:
        with open(os.path.join(ART, "audit.json"), "w") as f:
            json.dump(dict(suites=audits), f, indent=2)
    return failures


if __name__ == "__main__":
    main()
