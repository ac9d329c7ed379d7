"""Parallel build backend: bit-identicality of the epoch/merge protocol.

The ``parallel`` backend's contract is *exact* equivalence to the
sequential reference — entries AND pruning counters — for every worker
count, executor, DAG shaping, and pruning-flag ablation (the ablations
exercise all three validation paths: dirty-set version tracking with
PR2 on, content fingerprints with PR2 off, and the read-free path with
PR1 off). A forced-conflict configuration (no DAG edge analysis at
all) drives the stale-re-run repair machinery on purpose and must
still be exact. Scheduler/DAG/mirror units and the service + telemetry
integration ride along; the heavy cross-product lives under
``@pytest.mark.slow``.
"""
import os

import numpy as np
import pytest

from repro.build import build_rlc_index_with_stats, get_backend
from repro.build.parallel import (HubSliceMirror, ListScheduler,
                                  ParallelBackend, PhaseCostModel,
                                  PhaseDAG)
from repro.build.base import access_schedule
from repro.graphgen import (erdos_renyi, fig2_graph,
                            random_labeled_graph)

#: CI pins this to 2 so tier-1 exercises the protocol at fixed width
WORKERS = int(os.environ.get("RLC_PARALLEL_WORKERS", "2"))


def entry_sets(idx):
    out = tuple(sorted((v, h, m) for v, d in enumerate(idx.l_out)
                       for h, ms in d.items() for m in ms))
    inn = tuple(sorted((v, h, m) for v, d in enumerate(idx.l_in)
                       for h, ms in d.items() for m in ms))
    return out, inn


def assert_bit_identical(g, k, flags=None, **kw):
    flags = flags or {}
    ref_idx, ref_st = build_rlc_index_with_stats(g, k, backend="python",
                                                 **flags)
    kw.setdefault("workers", WORKERS)
    kw.setdefault("executor", "inline")
    be = ParallelBackend(**flags, **kw)
    idx, st = be.build(g, k)
    assert entry_sets(idx) == entry_sets(ref_idx), (flags, kw)
    assert st.counters() == ref_st.counters(), (flags, kw)
    return be


# ------------------------------------------------------------------ #
# Property sweep: V, |L|, k, loop density x workers x pruning flags
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("k,num_labels,loops", [
    (1, 2, 0.0), (2, 2, 0.2), (2, 3, 0.0), (3, 2, 0.3)])
def test_parallel_matches_python_random(seed, k, num_labels, loops):
    g = random_labeled_graph(num_vertices=14, num_edges=46,
                             num_labels=num_labels, seed=seed,
                             self_loop_frac=loops)
    assert_bit_identical(g, k)


@pytest.mark.parametrize("workers", [1, 2, 3, 4])
def test_parallel_worker_counts(workers):
    g = erdos_renyi(28, 2.5, 3, seed=5)
    be = assert_bit_identical(g, 2, workers=workers)
    info = be.last_build_info
    assert info["mode"] == ("sequential" if workers == 1 else "parallel")


@pytest.mark.parametrize("flags", [
    dict(use_pr2=False),                  # content-fingerprint path
    dict(use_pr1=False),                  # read-free phases
    dict(use_pr3=False),
    dict(use_pr1=False, use_pr2=False, use_pr3=False)])
def test_parallel_pruning_ablations(flags):
    g = random_labeled_graph(num_vertices=16, num_edges=52,
                             num_labels=2, seed=11, self_loop_frac=0.2)
    assert_bit_identical(g, 2, flags=flags)


def test_parallel_fig2_exact():
    g, _ = fig2_graph()
    be = assert_bit_identical(g, 2)
    assert be.last_build_info["mode"] in ("parallel", "sequential")


# ------------------------------------------------------------------ #
# Forced conflicts: no edge analysis -> speculation must mis-predict
# ------------------------------------------------------------------ #
def test_forced_conflicts_repair_exactly():
    """With the DAG stripped to intra-hub edges only (hot_prefix=0,
    locality=0) the scheduler speculates across real dependencies; the
    stale-re-run path must fire and the result must still be exact."""
    g = erdos_renyi(40, 2.5, 2, seed=3)
    be = assert_bit_identical(g, 2, workers=4, hot_prefix=0, locality=0,
                              auto_thin=False)
    info = be.last_build_info
    assert info["mode"] == "parallel"
    assert info["stale_reruns"] > 0
    assert info["epochs"] > 0


def test_process_executor_matches():
    g = erdos_renyi(24, 2.0, 3, seed=7)
    be = assert_bit_identical(g, 2, workers=2, executor="process")
    assert be.last_build_info["executor"] == "process"


def test_registered_backend_and_env_default(monkeypatch):
    monkeypatch.setenv("RLC_PARALLEL_WORKERS", "3")
    be = get_backend("parallel")
    assert isinstance(be, ParallelBackend) and be.workers == 3


# ------------------------------------------------------------------ #
# Units: DAG, scheduler, sliced mirror, accounting
# ------------------------------------------------------------------ #
def test_phase_dag_edges_point_forward():
    g = erdos_renyi(30, 2.0, 3, seed=1)
    order, _ = access_schedule(g)
    dag = PhaseDAG(g, 2, order)
    for p, preds in enumerate(dag.preds):
        assert all(q < p for q in preds)
    st = dag.stats(np.ones(dag.npos))
    assert st["phases"] > 0 and st["depth"] >= 1
    assert 0.0 < st["serial_fraction"] <= 1.0
    assert st["max_width"] >= st["mean_width"] > 0


def test_scheduler_plans_disjoint_and_windowed():
    g = erdos_renyi(40, 2.5, 3, seed=2)
    order, _ = access_schedule(g)
    dag = PhaseDAG(g, 2, order)
    cm = PhaseCostModel(np.ones(dag.npos))
    sched = ListScheduler(dag, cm, workers=3)
    committed = ~dag.active.copy()
    inflight = set()
    plans = []
    for _ in range(3):
        plan = sched.plan_for(committed, [], inflight, 0)
        assert plan == sorted(plan)
        assert not inflight.intersection(plan)
        assert all(p < ListScheduler.WINDOW for p in plan)
        inflight.update(plan)
        plans.append(plan)
    assert plans[0]     # frontier position is always dispatchable
    flat = [p for plan in plans for p in plan]
    assert len(flat) == len(set(flat))   # plans never overlap


def test_hub_slice_mirror_bytes_track():
    m = HubSliceMirror(num_mrs=3, num_vertices=64)
    assert m.size_bytes() == 0
    m.set1(m.out, 1, 5, 33)
    m.set1(m.in_, 2, 6, 12)
    n1 = m.size_bytes()
    assert n1 > 0 and m.peak_bytes == n1
    m.out.apply_mask(5, 1, 1 << 33)
    assert m.out.row_int(5, 1) == 1 << 33
    # running byte tally must equal a from-scratch walk
    expect = (len(m.out.blocks) * m.out.C * m.out.W
              + sum((v.bit_length() + 7) // 8 + 16
                    for d in m.out.rows.values() for v in d.values()))
    assert m.out.bytes_now() == expect
    m.out.clear_row(5)
    assert m.out.row_int(5, 1) == 0


def test_peak_mirror_bytes_recorded():
    g = erdos_renyi(30, 2.5, 3, seed=9)
    be = ParallelBackend(workers=2, executor="inline")
    _, st = be.build(g, 2)
    assert st.peak_mirror_bytes > 0
    info = be.last_build_info
    assert info["makespan_s"] > 0 or info["mode"] == "sequential"
    if info["mode"] == "parallel":
        assert len(info["worker_busy_s"]) == 2
        assert info["epochs"] >= 1


# ------------------------------------------------------------------ #
# Service + telemetry integration
# ------------------------------------------------------------------ #
def test_service_builds_with_parallel_backend():
    from repro.service import RLCService, ServiceConfig
    g = erdos_renyi(24, 2.0, 3, seed=4)
    svc = RLCService.build(g, ServiceConfig(k=2,
                                            build_backend="parallel"))
    ref, _ = build_rlc_index_with_stats(g, 2, backend="python")
    assert entry_sets(svc.index) == entry_sets(ref)
    # delta rebuilds degrade to a batched sequential backend
    assert svc._delta_backend_name() == "numpy"


def test_parallel_build_obs_series():
    from repro.obs import MetricsRegistry
    from repro.obs.build_obs import BuildPhaseObserver
    g = erdos_renyi(30, 2.5, 3, seed=6)
    reg = MetricsRegistry()
    obs = BuildPhaseObserver(reg, context="full")
    be = ParallelBackend(workers=2, executor="inline")
    be.set_observer(obs)
    be.build(g, 2)
    snap = reg.as_dict()
    if be.last_build_info["mode"] == "parallel":
        epochs = sum(s["value"]
                     for s in snap["rlc_build_epochs"]["series"])
        assert epochs == be.last_build_info["epochs"]
        assert snap["rlc_build_epoch_seconds"]["series"]
        workers = {s["labels"]["worker"] for s in
                   snap["rlc_build_worker_phase_seconds"]["series"]}
        assert workers   # at least one worker committed phases
    # per-phase series exist either way
    assert snap["rlc_build_phase_seconds"]["series"]


# ------------------------------------------------------------------ #
# Heavy sweep (nightly)
# ------------------------------------------------------------------ #
@pytest.mark.slow
@pytest.mark.parametrize("workers", [2, 4])
@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("k,num_labels,loops", [
    (2, 2, 0.2), (2, 4, 0.0), (3, 2, 0.0), (3, 3, 0.25), (4, 2, 0.1)])
def test_parallel_sweep_slow(workers, seed, k, num_labels, loops):
    g = random_labeled_graph(num_vertices=30, num_edges=110,
                             num_labels=num_labels, seed=seed,
                             self_loop_frac=loops)
    for flags in (dict(), dict(use_pr2=False),
                  dict(use_pr1=False, use_pr2=False, use_pr3=False)):
        assert_bit_identical(g, k, flags=flags, workers=workers)


@pytest.mark.slow
@pytest.mark.parametrize("seed", range(4))
def test_forced_conflict_sweep_slow(seed):
    g = erdos_renyi(50, 3.0, 3, seed=seed)
    assert_bit_identical(g, 2, workers=4, hot_prefix=0, locality=0,
                         auto_thin=False)


@pytest.mark.parametrize("module", ["repro.build.parallel.worker",
                                    "repro.service.rpc.worker",
                                    "repro.service.executor",
                                    "repro.service.sharded.replica"])
def test_worker_modules_do_not_import_jax(module):
    """Worker children import their module (an RPC shard worker also the
    executor and the replica slice helpers): none may load jax, or a
    child would contend with its parent for the accelerator."""
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    code = (f"import sys; import {module}; "
            "assert 'jax' not in sys.modules, sorted("
            "m for m in sys.modules if m.startswith('jax'))")
    r = subprocess.run([sys.executable, "-c", code], env=dict(
        os.environ, PYTHONPATH=src), capture_output=True, text=True,
        timeout=120)
    assert r.returncode == 0, r.stderr


def test_process_workers_never_fork_a_jax_parent(monkeypatch):
    import sys

    import jax  # noqa: F401 — this process is now a JAX parent
    from repro.build.parallel.worker import _start_method
    monkeypatch.delenv("RLC_PARALLEL_MP_CONTEXT", raising=False)
    assert _start_method() == "forkserver"
    monkeypatch.delitem(sys.modules, "jax")
    assert _start_method() == "fork"
    monkeypatch.setenv("RLC_PARALLEL_MP_CONTEXT", "spawn")
    assert _start_method() == "spawn"
