"""A run's answer check: sound runs read correct; the control and each
fault a query cell can have, planted under the timed path, read not
correct. A tiny graph on the CPU stands in for the cell's own; the
harness's look for a chip is skipped, the rest of the run is whole."""
import itertools
import time

import numpy as np
import pytest

from bench import control
from bench.lib import cell as cl


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    import repro.device
    monkeypatch.setattr(repro.device, "enable_compile_cache", lambda: "")


def tiny(traffic, trace=False):
    """The EP-shaped configuration at 600 vertices under ``traffic``; a
    traced run reports ``ep-batch``'s per-layer metrics."""
    bench = cl.load_json(cl.ROOT / "BENCHMARK.json")
    config = cl.load_json(cl.BENCH / "configs" / "ba-ep-4k.json")
    mix = cl.load_json(cl.BENCH / "traffic" / f"{traffic}.json")
    mix.update(pool=8192, warm_calls=1)
    return cl.Cell("tiny", 1, dict(config, vertices=600), mix,
                   cl.select_metrics(bench, "ep-batch", trace))


def run(traffic, hook=None, trace=False, tmp_path=None):
    return cl.execute(tiny(traffic, trace), 2**31 + 77, 0.5, trace,
                      time.perf_counter(), require_chip=False, hook=hook,
                      log_dir=str(tmp_path) if tmp_path else None)


def flip_one(run):
    """Each executed batch comes back with its first answer inverted."""
    inner = run.svc.executor.execute

    def execute(*a, **kw):
        ans, backend = inner(*a, **kw)
        ans = np.array(ans)
        ans[0] = ~ans[0]
        return ans, backend
    run.svc.executor.execute = execute


def half_left_out(run):
    """Each executed batch computes its first half only; the rest is
    answered False."""
    inner = run.svc.executor.execute

    def execute(s, t, mr_id, n_real=None, **kw):
        n = len(s) if n_real is None else n_real
        h = max(n // 2, 1)
        ans, backend = inner(s[:h], t[:h], mr_id[:h], h, **kw)
        return np.concatenate([ans, np.zeros(n - h, bool)]), backend
    run.svc.executor.execute = execute


def unwarmed_shape(run):
    """Each executed batch also runs a jitted op on a shape no call has
    had before, so the window compiles."""
    import jax
    import jax.numpy as jnp
    inner = run.svc.executor.execute
    op = jax.jit(lambda x: x + 1)
    shapes = itertools.count(1)

    def execute(*a, **kw):
        op(jnp.zeros(next(shapes))).block_until_ready()
        return inner(*a, **kw)
    run.svc.executor.execute = execute


MIXES = ["batch-shuffled"]


@pytest.mark.parametrize("traffic", MIXES)
def test_sound_run_is_correct(traffic):
    out = run(traffic)
    assert out["correct"], out["check"]
    assert out["check"]["wrong"] == {"value": 0, "limit": 0}
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("traffic", MIXES)
@pytest.mark.parametrize("fault", [control.truncate_rows, flip_one,
                                   half_left_out],
                         ids=["control_rows_cut", "answer_flipped",
                              "half_batch_left_out"])
def test_control_and_faults_are_not_correct(traffic, fault):
    out = run(traffic, hook=fault)
    assert not out["correct"]
    assert out["check"]["wrong"]["value"] > 0


def test_a_compile_inside_the_window_is_not_correct():
    out = run("batch-shuffled", hook=unwarmed_shape)
    assert out["check"]["wrong"]["value"] == 0
    assert out["check"]["compiles_in_window"]["value"] > 0
    assert not out["correct"]


def test_sound_run_compiles_nothing_in_the_window():
    out = run("batch-shuffled")
    assert out["check"]["compiles_in_window"] == {"value": 0, "limit": 0}


def test_traced_run_keeps_the_result_line(tmp_path):
    from bench.run import result_line
    out = run("batch-shuffled", trace=True, tmp_path=tmp_path)
    line = result_line(out)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "check"]
    assert line["correct"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s", "platform", "kind", "count",
            "memory_peak_bytes"} <= set(line["device"])
    # the CPU has no device plane: device readers stay silent
    assert "device_idle" not in line["metrics"]
    assert "mergejoin_roofline" not in line["metrics"]
    for name in ("admit_us", "batch_fill", "exec_ms_p50", "layout_s"):
        assert line["metrics"][name]["value"] > 0
