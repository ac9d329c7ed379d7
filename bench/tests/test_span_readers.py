"""The phase-span share readers: their arithmetic on hand-made runs,
their silence where the program has no spans, and a traced run on the
CPU that reports all six."""
import time
from types import SimpleNamespace

import pytest

from bench.lib import cell as cl

SHARES = {"admit_pct": "admit", "exec_h2d_pct": "exec.h2d",
          "exec_dispatch_pct": "exec.dispatch", "exec_wait_pct": "exec.wait",
          "exec_d2h_pct": "exec.d2h", "answer_pct": "answer"}


def reader(name):
    return cl.load_module(cl.BENCH / "metrics" / f"{name}.py").read


def _run(totals, window_s=4.0):
    """A run whose ``rlc_span_seconds`` series hold ``totals`` seconds,
    one sample each."""
    def pick(name, span):
        return [totals[span]] if (name == "rlc_span_seconds"
                                  and span in totals) else []
    return SimpleNamespace(
        window_s=window_s,
        hist_samples=lambda name, span=None: pick(name, span),
        hist_total=lambda name, span=None: sum(pick(name, span)))


@pytest.mark.parametrize("metric, span", sorted(SHARES.items()))
def test_share_of_the_window(metric, span):
    run = _run({s: 0.1 * (i + 1) for i, s in enumerate(SHARES.values())})
    i = list(SHARES.values()).index(span)
    assert reader(metric)(run) == pytest.approx(100 * 0.1 * (i + 1) / 4.0)


@pytest.mark.parametrize("metric", sorted(SHARES))
def test_silent_without_the_span(metric):
    assert reader(metric)(_run({})) is None
    assert reader(metric)(_run({s: 1.0 for s in SHARES.values()},
                               window_s=0.0)) is None


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    import repro.device
    monkeypatch.setattr(repro.device, "enable_compile_cache", lambda: "")


def test_traced_run_reports_the_six_shares(tmp_path):
    bench = cl.load_json(cl.ROOT / "BENCHMARK.json")
    config = cl.load_json(cl.BENCH / "configs" / "ba-ep-4k.json")
    mix = cl.load_json(cl.BENCH / "traffic" / "batch-shuffled.json")
    mix.update(pool=8192, warm_calls=1)
    cell = cl.Cell("tiny", 1, dict(config, vertices=600), mix,
                   cl.select_metrics(bench, "ep-batch", True))
    out = cl.execute(cell, 2**31 + 78, 0.5, True, time.perf_counter(),
                     require_chip=False, log_dir=str(tmp_path))
    assert out["correct"], out["check"]
    got = {m: out["metrics"][m]["value"] for m in SHARES}
    assert all(v > 0 for v in got.values()), got
    # the six phases never overlap, so they cover at most the window
    assert sum(got.values()) <= 100.0
