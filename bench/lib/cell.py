"""One run of one cell: set-up, the measured window, the answer check.

Everything a cell is made of is found by name:

* the cell itself in ``BENCHMARK.json`` (its configuration, traffic and
  chips);
* ``bench/configs/<config>.json``: the deployment (graph generator and
  sizes, ``k``, the ``ServiceConfig`` fields it sets);
* ``bench/traffic/<traffic>.json``: the mix (pool, its share of true
  queries, loop, call size, the service fields the mix's clients set);
* ``bench/loops/<loop>.py``: the driver the mix names (``prepare`` and
  ``window``);
* ``bench/graphs/<generator>.py``: a graph generator the configuration
  names, other than the Barabasi-Albert copy in ``lib/graph.py``;
* ``bench/metrics/<metric>.py``: one reader per metric (``read(run)``).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from . import traffic as tf
from .graph import apply_write, make_edges
from .reference import Reference

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
#: distinct queries, drawn from the seed among those answered in the
#: window, whose every answer is compared with the reference
CHECK_QUERIES = 2048


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: List[dict]          # BENCHMARK.json entries this run reports

    @staticmethod
    def from_benchmark(bench: dict, name: str, trace: bool) -> "Cell":
        specs = {w["name"]: w for w in bench["workloads"]}
        if name not in specs:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"choose from {sorted(specs)}")
        spec = specs[name]
        cfg_file = {c["name"]: c["file"] for c in bench["configs"]}
        config = load_json(ROOT / cfg_file[spec["config"]])
        traffic = load_json(BENCH / "traffic" / f"{spec['traffic']}.json")
        return Cell(name, spec["chips"], config, traffic,
                    select_metrics(bench, name, trace))


def select_metrics(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The end-to-end metrics the cell reports (untraced run), or its
    per-layer metrics (traced run)."""
    def applies(m):
        return "workloads" not in m or cell in m["workloads"]
    e2e = [m for m in bench["end_to_end"] if applies(m)]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


@dataclass
class Run:
    """What a run measured; the metric readers take their numbers from
    it."""

    cell: Cell
    seed: int
    seconds: float
    svc: object = None
    pool: Optional[tf.Pool] = None
    #: the edges drawn for the run, before any write
    edges: np.ndarray = field(default_factory=lambda: np.zeros((0, 3), int))
    device_kind: str = ""
    row_len: int = 0
    setup_s: float = 0.0
    index_s: float = 0.0
    layout_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    answered: int = 0
    failed: int = 0
    #: pool index and answer of every request answered in the window
    served: np.ndarray = field(default_factory=lambda: np.zeros(0, int))
    answers: np.ndarray = field(default_factory=lambda: np.zeros(0, bool))
    #: (start, end) on the host clock of each call the window made
    calls: List[tuple] = field(default_factory=list)
    #: writes the traffic asks for in the window; the ``(inserts,
    #: deletes)`` rows of each applied, in order, and its seconds from
    #: the call to the return
    writes_due: int = 0
    writes: List[tuple] = field(default_factory=list)
    write_s: List[float] = field(default_factory=list)
    #: per answer in ``served``: how many writes had returned before its
    #: call began (all 0 when empty)
    epochs: np.ndarray = field(default_factory=lambda: np.zeros(0, int))
    #: pool indices of the queries the writes change, and of the probes
    #: of the rows they touch: every answer to either is compared
    flips: np.ndarray = field(default_factory=lambda: np.zeros(0, int))
    probes: np.ndarray = field(default_factory=lambda: np.zeros(0, int))
    #: seconds of set-up spent in the plain reference for the window's
    #: traffic (drawing writes): kept out of ``setup_s``
    reference_s: float = 0.0
    trace: object = None             # lib.trace.TraceSummary
    counters0: Dict[tuple, float] = field(default_factory=dict)
    spans: bool = False               # the loop adds bench:* spans

    # -- registry reads, over the window ------------------------------- #
    def _series(self, name: str, labels: dict):
        m = self.svc.obs.registry.get(name)
        if m is None:
            return
        for key, cell in m.series():
            lab = dict(zip(m.labelnames, key))
            if all(lab.get(k) == v for k, v in labels.items()):
                yield key, cell

    def counter_delta(self, name: str, **labels) -> float:
        return sum(cell.value - self.counters0.get((name, key), 0.0)
                   for key, cell in self._series(name, labels))

    def hist_samples(self, name: str, **labels) -> List[float]:
        return [v for _, cell in self._series(name, labels)
                for v in cell.reservoir.samples]

    def hist_total(self, name: str, **labels) -> float:
        return sum(cell.reservoir.total
                   for _, cell in self._series(name, labels))


def _window_start(run: Run) -> None:
    """Start every histogram afresh and note every counter, so that the
    readers see the window alone."""
    from repro.obs import Reservoir
    reg = run.svc.obs.registry
    for name, m in reg.metrics().items():
        for key, cell in m.series():
            if hasattr(cell, "reservoir"):
                cell.reservoir = Reservoir(cell.reservoir.cap)
            else:
                run.counters0[(name, key)] = cell.value


def warm_shapes(svc, batch_size: int) -> None:
    """Run each power-of-two batch shape up to ``batch_size`` once: the
    executor pads every batch to one of them."""
    n = 1
    while n <= batch_size:
        z = np.zeros(n, np.int32)
        svc.executor.execute(z, z, z)
        n *= 2


def layout_ready(svc) -> None:
    """Wait until the service's device layout is on the device."""
    import jax
    d = svc.device_index
    jax.block_until_ready([d.out_hub, d.out_mr, d.in_hub, d.in_mr,
                           d.out_key, d.in_key])


def add_spans(svc) -> None:
    """Wrap the executor call of this service in a ``bench:execute``
    span (traced runs only)."""
    import jax.profiler as jp
    inner = svc.executor.execute

    def execute(*a, **kw):
        with jp.TraceAnnotation("bench:execute"):
            return inner(*a, **kw)
    svc.executor.execute = execute


class WindowWatch:
    """What happens in the process while ``on``: JAX's trace and compile
    events, of which the window must hold none, and Python's garbage
    collections, which stall the host."""

    def __init__(self):
        import jax.monitoring as mon
        self.on = False
        self.compiles = 0
        self.compile_s = 0.0
        #: (start, seconds, generation) of each collection
        self.collections: List[tuple] = []
        self._gc_t0 = 0.0
        mon.register_event_duration_secs_listener(self._event)
        gc.callbacks.append(self._gc)

    def _event(self, name: str, secs: float, **_kw) -> None:
        if self.on and name.startswith("/jax/core/compile/"):
            self.compiles += 1
            self.compile_s += secs

    def _gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self.on:
            self.collections.append((self._gc_t0, time.perf_counter()
                                     - self._gc_t0, info["generation"]))

    def close(self) -> None:
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._event)
        gc.callbacks.remove(self._gc)

    def report(self, run: "Run") -> None:
        """Print the window's compile events and collections, and how
        much collection fell inside the calls that took over twice the
        median call."""
        print(f"window: {run.window_s:.3f} s, {run.answered} answered; "
              f"{self.compiles} trace and compile events inside it "
              f"({self.compile_s:.3f} s)", file=sys.stderr, flush=True)
        by_gen: Dict[int, tuple] = {}
        for _, secs, g in self.collections:
            n, tot, top = by_gen.get(g, (0, 0.0, 0.0))
            by_gen[g] = (n + 1, tot + secs, max(top, secs))
        print("collections in the window: " + ("; ".join(
            f"gen{g} n={n} total={tot:.4f} s max={top:.4f} s"
            for g, (n, tot, top) in sorted(by_gen.items())) or "none"),
            file=sys.stderr, flush=True)
        if not run.calls:
            return
        spans = np.asarray(run.calls)
        took = spans[:, 1] - spans[:, 0]
        slow = spans[took > 2 * np.median(took)]
        inside = sum(secs for t, secs, _ in self.collections
                     if any(a <= t < b for a, b in slow))
        print(f"calls over twice the median ({np.median(took) * 1e3:.2f} "
              f"ms): {len(slow)} of {len(spans)}, "
              f"{(slow[:, 1] - slow[:, 0]).sum():.3f} s, of which "
              f"{inside:.3f} s in collections", file=sys.stderr, flush=True)


def execute(cell: Cell, seed: int, seconds: float, trace: bool,
            t_start: float, require_chip: bool = True,
            hook: Optional[Callable] = None, log_dir: Optional[str] = None
            ) -> dict:
    """Set up, measure, check; returns the result line's object.

    ``hook(run)`` runs once the service is built and before anything is
    warmed: the control and the fault tests put a broken path in there.
    """
    import jax
    from repro.build import build_rlc_index_with_stats
    from repro.core.graph import LabeledGraph
    from repro.device import enable_compile_cache
    from repro.service import RLCService, ServiceConfig

    from . import trace as trace_lib

    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform != "tpu" or len(devices) < cell.chips):
        raise NoChip(f"JAX found {len(devices)} {dev.platform} device(s); "
                     f"the cell needs {cell.chips} TPU chip(s)")
    run = Run(cell, seed, seconds, device_kind=dev.device_kind)
    cfg = cell.config
    edges = make_edges(cfg, seed)
    graph = LabeledGraph.from_edges(cfg["vertices"], cfg["labels"], edges)
    scfg = ServiceConfig(k=cfg["k"], **cfg.get("service", {}),
                         **cell.traffic.get("service", {}))
    t0 = time.perf_counter()
    index, _ = build_rlc_index_with_stats(graph, scfg.k,
                                          backend=scfg.build_backend)
    run.index_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    svc = RLCService.build(graph, scfg, index=index)
    layout_ready(svc)
    run.layout_s = time.perf_counter() - t0
    run.svc, run.row_len, run.edges = svc, svc.device_index.row_len, edges
    run.pool = tf.make_pool(cfg["vertices"], edges, scfg.k,
                            cell.traffic["pool"],
                            cell.traffic["walk_share"],
                            tf.stream(seed, tf.POOL))
    if hook is not None:
        hook(run)
    if trace:
        add_spans(svc)
        run.spans = True
    loop = load_module(BENCH / "loops" / f"{cell.traffic['loop']}.py")
    watch = WindowWatch()
    try:
        warm_shapes(svc, scfg.batch_size)
        state = loop.prepare(run)
        _window_start(run)
        # set-up leaves many young objects (graph, pool, index); a full
        # collection now, in set-up, keeps their first sweep out of the
        # window, where it stalled one call by 100 ms or more
        gc.collect()
        run.setup_s = time.perf_counter() - t_start - run.reference_s
        watch.on = True
        if trace:
            with trace_lib.capture(log_dir) as found:
                with jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
                    loop.window(run, state)
                watch.on = False
            import jax.profiler as jp
            run.trace = trace_lib.reduce(
                jp.ProfileData.from_file(found[0]))
        else:
            loop.window(run, state)
            watch.on = False
    finally:
        watch.close()
        svc.close()
    watch.report(run)
    peak = max((x.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for x in devices[:cell.chips])
    metrics = {}
    for m in cell.metrics:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            metrics[m["name"]] = dict(value=float(value), unit=m["unit"])
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(devices), memory_peak_bytes=int(peak))
    out = dict(attempted=run.attempted, failed=run.failed,
               metrics=metrics, device=device)
    if run.trace is not None:
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
        out["breakdown"] = dict(device_ops=run.trace.top_ops(),
                                idle_gaps=run.trace.top_gaps())
    run.svc = svc = None
    check = check_answers(run)
    check["compiles_in_window"] = dict(value=watch.compiles, limit=0)
    out["correct"] = all(c["value"] <= c["limit"] for c in check.values())
    out["check"] = check
    return out


def check_answers(run: Run) -> Dict[str, dict]:
    """Compare the window's answers with the plain reference.

    Draws ``CHECK_QUERIES`` distinct queries from the seed among those
    answered, and compares every answer the window gave to each of them,
    cached answers included; every answer to a query that a write
    changes, or that probes a row it touches, too. Each answer is
    compared with the reference over the graph as it stood when its call
    began: ``run.edges`` with as many of ``run.writes`` applied as had
    returned. Each number comes with its limit.
    """
    rng = tf.stream(run.seed, tf.SAMPLE)
    flip = np.isin(run.served, run.flips)
    probe = np.isin(run.served, run.probes)
    answered = np.unique(run.served[~(flip | probe)])
    picks = rng.choice(answered, size=min(CHECK_QUERIES, len(answered)),
                       replace=False) if len(answered) else answered
    epochs = (run.epochs if len(run.epochs)
              else np.zeros(len(run.served), int))
    sel = np.isin(run.served, picks) | flip | probe
    pairs = set(zip(run.served[sel].tolist(), epochs[sel].tolist()))
    want = {}
    graph = run.edges
    for e in range(max((e for _, e in pairs), default=0) + 1):
        if e:
            graph = apply_write(graph, *run.writes[e - 1])
        idx = sorted(i for i, x in pairs if x == e)
        ref = Reference(run.cell.config["vertices"], graph)
        want.update(zip(((i, e) for i in idx),
                        ref.answers(run.pool.queries(idx)).tolist()))
    expect = np.array([want[p] for p in zip(run.served[sel].tolist(),
                                            epochs[sel].tolist())], bool)
    bad = run.answers[sel] != expect
    wrong = int(bad.sum())
    # each pick as the reference answered it when first compared
    first = {}
    for i, e in zip(run.served[sel].tolist(), epochs[sel].tolist()):
        first.setdefault(i, e)
    walk = picks < run.pool.n_walk
    ref_true = np.array([want[i, first[i]] for i in picks.tolist()], bool)
    print(f"compared {int(sel.sum())} answers to {len(picks)} distinct "
          f"queries with the reference; reference says true for "
          f"{ref_true[walk].sum()} of {walk.sum()} from walks, "
          f"{ref_true[~walk].sum()} of {(~walk).sum()} drawn false",
          file=sys.stderr, flush=True)
    check = dict(
        wrong=dict(value=wrong, limit=0),
        unanswered=dict(value=run.attempted - run.answered, limit=0),
        failed=dict(value=run.failed, limit=0),
        compared_none=dict(value=int(sel.sum() == 0), limit=0),
    )
    if not run.writes_due:
        return check
    on, near = flip[sel], probe[sel]
    per_epoch = np.bincount(epochs[sel], minlength=run.writes_due + 1)
    print(f"writes: {len(run.writes)} of {run.writes_due} applied; "
          f"answers compared by epoch {per_epoch.tolist()}; "
          f"{int(on.sum())} answers to {len(np.unique(run.served[flip]))} "
          f"distinct queries that a write changes, {int(bad[on].sum())} "
          f"wrong; {int(near.sum())} answers to "
          f"{len(np.unique(run.served[probe]))} probes of the rows it "
          f"touches, {int(bad[near].sum())} wrong",
          file=sys.stderr, flush=True)
    check.update(
        writes_missing=dict(value=run.writes_due - len(run.writes),
                            limit=0),
        after_write_none=dict(value=int(per_epoch[len(run.writes)] == 0),
                              limit=0),
        flips_none=dict(value=int(not on.any()), limit=0),
    )
    return check


def report_check(out: dict) -> None:
    for name, c in out["check"].items():
        print(f"check {name}={c['value']} limit={c['limit']}",
              file=sys.stderr, flush=True)
