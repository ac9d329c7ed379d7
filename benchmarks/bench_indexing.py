"""Paper Table IV: indexing time (IT) and index size (IS), RLC vs ETC —
plus the build-backend axis added with the staged build pipeline.

Reproduces the paper's claim set on scaled-down stand-ins of its graphs:
the RLC index builds orders of magnitude faster and smaller than the
extended transitive closure; pruning rules drive both gaps. The backend
axis then measures the same build through each :mod:`repro.build`
backend (python reference vs batched numpy vs pallas), asserting entry
equality and reporting per-graph + aggregate speedups, and scales the
parallel epoch/merge backend across 1/2/4 workers. Results land in the
orchestrator CSV and ``benchmarks/artifacts/indexing.json``.

The pallas backend only *interprets* its kernels on CPU (hours, not
seconds) — the backend axis includes it only when a real accelerator
backs jax, and validates it on a tiny stand-in otherwise.
"""
from __future__ import annotations

import gc
import json
import os
import time

from repro.build import build_rlc_index_with_stats, get_backend
from repro.build.parallel import ParallelBackend
from repro.core.baselines import ETC
from repro.device import on_cpu

from .common import PAPER_GRAPH_STANDINS, Report, standin_graph

ART = os.path.join(os.path.dirname(__file__), "artifacts")


def _quick_names(quick: bool):
    names = [n for n, *_ in PAPER_GRAPH_STANDINS]
    return names[:3] if quick else names


def run(quick: bool = True, smoke: bool = False, k: int = 2) -> Report:
    rep = Report("indexing.tableIV")
    names = ["AD", "TW"] if smoke else _quick_names(quick)
    scale = 0.3 if smoke else 1.0
    for name in names:
        g = standin_graph(name, scale=scale)
        t0 = time.perf_counter()
        idx, stats = build_rlc_index_with_stats(g, k, backend="python")
        rlc_it = time.perf_counter() - t0
        t0 = time.perf_counter()
        etc = ETC(g, k)
        etc_it = time.perf_counter() - t0
        rep.add(graph=name, V=g.num_vertices, E=g.num_edges,
                L=g.num_labels, loops=g.loop_count(),
                rlc_it_s=round(rlc_it, 3),
                rlc_is_bytes=idx.size_bytes(),
                rlc_entries=idx.num_entries(),
                etc_it_s=round(etc_it, 3),
                etc_is_bytes=etc.size_bytes(),
                etc_entries=etc.num_entries(),
                it_speedup=round(etc_it / max(rlc_it, 1e-9), 1),
                is_ratio=round(etc.size_bytes()
                               / max(idx.size_bytes(), 1), 1),
                pr1=stats.pruned_pr1, pr2=stats.pruned_pr2,
                pr3=stats.pr3_cuts)
    return rep


def run_pruning_ablation(smoke: bool = False, k: int = 2) -> Report:
    """Paper's pruning-impact observation: build with/without PR rules."""
    rep = Report("indexing.pruning")
    g = standin_graph("AD", scale=0.3 if smoke else 1.0)
    for flags, label in [
            (dict(), "pr123"),
            (dict(use_pr1=False), "no-pr1"),
            (dict(use_pr3=False), "no-pr3"),
            (dict(use_pr1=False, use_pr2=False, use_pr3=False), "none")]:
        t0 = time.perf_counter()
        idx, stats = build_rlc_index_with_stats(g, k, backend="python",
                                                **flags)
        rep.add(variant=label, it_s=round(time.perf_counter() - t0, 3),
                entries=idx.num_entries(),
                searched=stats.kernel_search_states
                + stats.kernel_bfs_states)
    return rep


# --------------------------------------------------------------------- #
# Worker-scaling axis (parallel epoch/merge backend)
# --------------------------------------------------------------------- #
WORKER_AXIS = (1, 2, 4)


def _parallel_scaling(rep, summary, graphs, refs, numpy_s, k,
                      repeats) -> None:
    """Parallel-backend build at 1/2/4 workers on the same stand-ins.

    w=1 takes the sequential fast path (measured wall time); w>1 uses
    the coordinator's virtual-time ``makespan_s`` — the executor runs
    workers inline and sequences completions on a virtual timeline, so
    the number is the modeled parallel wall time and stays meaningful
    on boxes with fewer cores than workers (this one may have 1). Each
    measurement is best-of-``repeats`` and asserted entry- and
    counter-identical to the python reference. The headline
    ``parallel_speedup`` is aggregate numpy wall over aggregate
    max-worker makespan.
    """
    ptotals = {w: 0.0 for w in WORKER_AXIS}
    par_rows = []
    for name, g in graphs.items():
        prow = dict(graph=name)
        binfo = {}
        for w in WORKER_AXIS:
            best, built = None, None
            for _ in range(repeats):
                be = ParallelBackend(workers=w, executor="inline")
                gc.collect()   # same hygiene as the backend loop
                t0 = time.perf_counter()
                idx, stats = be.build(g, k)
                wall = time.perf_counter() - t0
                info = be.last_build_info
                dt = (info["makespan_s"]
                      if info.get("mode") == "parallel" else wall)
                if best is None or dt < best:
                    best = dt
                built = (idx.num_entries(), stats.counters())
                if w == WORKER_AXIS[-1]:
                    binfo = info
            if built != refs[name]:
                raise AssertionError(
                    f"parallel(w={w}) diverged from python on {name}: "
                    f"{built} != {refs[name]}")
            ptotals[w] += best
            prow[f"w{w}_s"] = round(best, 4)
        wmax = WORKER_AXIS[-1]
        prow["speedup_vs_numpy"] = round(
            numpy_s[name] / max(prow[f"w{wmax}_s"], 1e-9), 2)
        prow["epochs"] = binfo.get("epochs", 0)
        prow["stale_reruns"] = binfo.get("stale_reruns", 0)
        prow["thinned"] = bool(binfo.get("thinned", False))
        rep.add(**prow)
        prow["dag"] = binfo.get("dag", {})   # width/depth/serial_frac
        par_rows.append(prow)
    wmax = WORKER_AXIS[-1]
    summary["parallel"] = dict(
        workers=list(WORKER_AXIS), executor="inline",
        model="virtual-makespan", cpu_count=os.cpu_count(),
        aggregate_s={str(w): round(ptotals[w], 4) for w in WORKER_AXIS},
        rows=par_rows)
    summary["parallel_speedup"] = round(
        summary["aggregate_s"]["numpy"] / max(ptotals[wmax], 1e-9), 2)
    rep.add(graph="AGGREGATE",
            **{f"w{w}_s": round(ptotals[w], 4) for w in WORKER_AXIS},
            parallel_speedup=summary["parallel_speedup"])


# --------------------------------------------------------------------- #
# Build-backend axis (staged pipeline: python vs numpy vs pallas)
# --------------------------------------------------------------------- #
def run_backends(quick: bool = True, smoke: bool = False, k: int = 2,
                 scale: float = 1.0, repeats: int = 2) -> Report:
    """Per-backend build times on the stand-ins + equality check.

    Emits ``artifacts/indexing.json`` with per-graph rows, per-backend
    aggregate wall time, the numpy-vs-python aggregate speedup, and the
    worker-scaling axis of the parallel backend (``parallel_speedup``
    headline + per-graph DAG shape stats — see
    :func:`_parallel_scaling`).
    """
    rep = Report("indexing.backends")
    if smoke:
        scale = min(scale, 0.3)
        repeats = 1
    backends = ["python", "numpy"]
    if not on_cpu():
        backends.append("pallas")
    totals = {b: 0.0 for b in backends}
    json_rows = []
    graphs, refs, numpy_s = {}, {}, {}
    for name in _quick_names(quick):
        g = graphs[name] = standin_graph(name, scale=scale)
        row = dict(graph=name, V=g.num_vertices, E=g.num_edges,
                   L=g.num_labels)
        entries = {}
        for b in backends:
            best = None
            for _ in range(max(1, repeats)):
                backend = get_backend(b)
                gc.collect()   # a pause inside a ~0.1 s build sample
                # is pure noise; collect between, not during
                t0 = time.perf_counter()
                idx, stats = backend.build(g, k)
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            totals[b] += best
            entries[b] = (idx.num_entries(), stats.counters())
            row[f"{b}_s"] = round(best, 4)
        ref = refs[name] = entries["python"]
        numpy_s[name] = row["numpy_s"]
        for b in backends[1:]:
            if entries[b] != ref:
                raise AssertionError(
                    f"backend {b} diverged from python on {name}: "
                    f"{entries[b]} != {ref}")
            row[f"{b}_speedup"] = round(row["python_s"]
                                        / max(row[f"{b}_s"], 1e-9), 2)
        rep.add(**row)
        json_rows.append(row)
    agg = {b: round(totals[b], 4) for b in backends}
    summary = dict(graphs=_quick_names(quick), k=k, scale=scale,
                   aggregate_s=agg,
                   numpy_aggregate_speedup=round(
                       agg["python"] / max(agg["numpy"], 1e-9), 2),
                   pallas_included=("pallas" in backends),
                   rows=json_rows)
    # parallel builds are sub-second, so extra repeats are cheap — and
    # best-of-N is the only defense against scheduler noise on the
    # shared CI/container boxes these numbers come from
    _parallel_scaling(rep, summary, graphs, refs, numpy_s, k,
                      max(1, repeats) + 3)
    # CPU: validate the pallas backend end-to-end on a tiny stand-in so
    # the artifact always records a kernel-path build.
    if "pallas" not in backends:
        g = standin_graph("TW", scale=0.05)
        t0 = time.perf_counter()
        pidx, pstats = get_backend("pallas", mode="vector").build(g, k)
        ridx, rstats = get_backend("python").build(g, k)
        assert (pidx.num_entries(), pstats.counters()) == \
               (ridx.num_entries(), rstats.counters())
        summary["pallas_smoke"] = dict(
            V=g.num_vertices, E=g.num_edges, mode="interpret",
            s=round(time.perf_counter() - t0, 3),
            entries=pidx.num_entries())
        rep.add(graph="TW@0.05(pallas)", V=g.num_vertices, E=g.num_edges,
                L=g.num_labels, pallas_s=summary["pallas_smoke"]["s"])
    os.makedirs(ART, exist_ok=True)
    with open(os.path.join(ART, "indexing.json"), "w") as f:
        json.dump(summary, f, indent=2)
    rep.add(graph="AGGREGATE", **{f"{b}_s": agg[b] for b in backends},
            numpy_speedup=summary["numpy_aggregate_speedup"])
    return rep
