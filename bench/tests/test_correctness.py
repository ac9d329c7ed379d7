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


#: the mixes a tiny run takes: the cells' own, and a test mix that
#: writes (no cell runs one yet)
MIXES = {"batch-shuffled": cl.BENCH / "traffic" / "batch-shuffled.json",
         "write-stream": cl.BENCH / "tests" / "fixtures" / "write-stream.json"}

#: the metrics of a mix that writes, as a cell of one would list them
WRITE_METRICS = dict(
    end_to_end=[dict(name="fresh_ms", unit="ms"),
                dict(name="qps_between_writes", unit="queries/s")],
    per_layer=[dict(name="delta_fallback_pct", unit="%"),
               dict(name="delta_build_ms", unit="ms"),
               dict(name="delta_rest_ms", unit="ms")])


def tiny(traffic, trace=False):
    """The EP-shaped configuration at 600 vertices under ``traffic``,
    reporting ``ep-batch``'s metrics; under writes, those of a write
    cell in place of ``qps``."""
    bench = cl.load_json(cl.ROOT / "BENCHMARK.json")
    config = cl.load_json(cl.BENCH / "configs" / "ba-ep-4k.json")
    mix = cl.load_json(MIXES[traffic])
    mix.update(pool=8192, warm_calls=1)
    metrics = cl.select_metrics(bench, "ep-batch", trace)
    if "writes_per_s" in mix:
        keep = {"index_s", "setup_s", "layout_s"}
        metrics = [m for m in metrics if m["name"] in keep] + WRITE_METRICS[
            "per_layer" if trace else "end_to_end"]
    return cl.Cell("tiny", 1, dict(config, vertices=600), mix, metrics)


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


@pytest.mark.parametrize("traffic", MIXES)
def test_sound_run_is_correct(traffic):
    out = run(traffic)
    assert out["correct"], out["check"]
    assert out["check"]["wrong"] == {"value": 0, "limit": 0}
    assert out["attempted"] > 0 and out["failed"] == 0
    writes = traffic == "write-stream"
    assert ("fresh_ms" in out["metrics"]) == writes
    assert ("qps" in out["metrics"]) != writes
    if writes:
        assert out["check"]["writes_missing"] == {"value": 0, "limit": 0}
        assert out["metrics"]["qps_between_writes"]["value"] > 0


#: each mix's control (a write rebuilds the layout, so only the stale
#: control reaches one under writes) and the faults a query cell can have
FAULTS = [(control.truncate_rows, "control_rows_cut", "batch-shuffled"),
          (control.stale_writes, "control_stale", "write-stream")] + [
    (fault, name, traffic) for fault, name in (
        (flip_one, "answer_flipped"), (half_left_out, "half_batch_left_out"))
    for traffic in MIXES]


@pytest.mark.parametrize("traffic, fault", [
    pytest.param(traffic, fault, id=f"{name}-{traffic}")
    for fault, name, traffic in FAULTS])
def test_control_and_faults_are_not_correct(traffic, fault):
    out = run(traffic, hook=fault)
    assert not out["correct"]
    assert out["check"]["wrong"]["value"] > 0


@pytest.mark.parametrize("traffic, fault", [
    ("batch-shuffled", control.truncate_rows),
    ("write-stream", control.stale_writes)])
def test_the_control_follows_the_traffic(traffic, fault):
    assert control.control(cl.load_json(MIXES[traffic]))[0] is fault


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
