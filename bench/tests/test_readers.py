"""The peak table, the kernel's bytes, and the readers' arithmetic on
hand-made runs."""
from types import SimpleNamespace

import pytest

from bench.lib import cell as cl
from bench.lib.peaks import PEAKS, peak
from bench.lib.trace import TraceSummary


def reader(name):
    return cl.load_module(cl.BENCH / "metrics" / f"{name}.py").read


def test_v5e_peaks_with_source():
    p = peak("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in p["source"]
    assert all("source" in row for row in PEAKS.values())


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peak("TPU v99")


def test_join_bytes_are_one_out_row_and_one_in_row():
    mod = cl.load_module(cl.BENCH / "metrics" / "mergejoin_roofline.py")
    assert mod.join_bytes(1, 320) == 2 * 2 * 4 * 320
    assert mod.join_bytes(1024, 24) == 1024 * 16 * 24


def _run(**kw):
    base = dict(trace=None, device_kind="TPU v5 lite", row_len=320,
                window_s=2.0, answered=1000)
    base.update(kw)
    run = SimpleNamespace(**base)
    counts = kw.get("counts", {})
    run.counter_delta = lambda name, **lab: counts.get(
        (name, tuple(sorted(lab.items()))), 0.0)
    run.hist_total = lambda name, **lab: kw.get("hist_total", 0.0)
    run.hist_samples = lambda name, **lab: kw.get("samples", [])
    return run


def _trace(kernel_s, busy_s=0.5, window_s=2.0, devices=1):
    return TraceSummary(window_s=window_s, devices=devices, busy_s=busy_s,
                        op_seconds={"rlc_mergejoin.1": kernel_s,
                                    "copy.4": 0.1})


def test_roofline_share():
    counts = {("rlc_executor_queries", (("backend", "pallas"),)): 1e6}
    run = _run(trace=_trace(0.01), counts=counts)
    least = 1e6 * 16 * 320 / 819e9
    assert reader("mergejoin_roofline")(run) == pytest.approx(
        100 * least / 0.01)


@pytest.mark.parametrize("trace", [None, _trace(0.0), _trace(0.01, devices=0)])
def test_roofline_silent_without_kernel_time(trace):
    counts = {("rlc_executor_queries", (("backend", "pallas"),)): 1e6}
    assert reader("mergejoin_roofline")(_run(trace=trace,
                                             counts=counts)) is None


def test_roofline_unknown_device_is_an_error():
    counts = {("rlc_executor_queries", (("backend", "pallas"),)): 1e6}
    with pytest.raises(KeyError):
        reader("mergejoin_roofline")(_run(trace=_trace(0.01), counts=counts,
                                          device_kind="TPU v99"))


def test_device_idle():
    run = _run(trace=_trace(0.01, busy_s=0.5, window_s=2.0))
    assert reader("device_idle")(run) == pytest.approx(75.0)
    assert reader("device_idle")(_run()) is None


def test_qps_and_admission():
    run = _run(hist_total=0.5)
    assert reader("qps")(run) == pytest.approx(500.0)
    assert reader("admit_us")(run) == pytest.approx(1500.0)
    assert reader("qps")(_run(window_s=0.0)) is None


def test_batch_fill_and_exec_median():
    counts = {("rlc_executor_queries", ()): 900.0,
              ("rlc_executor_batches", ()): 10.0}
    run = _run(counts=counts, samples=[0.001, 0.003, 0.002])
    assert reader("batch_fill")(run) == pytest.approx(90.0)
    assert reader("exec_ms_p50")(run) == pytest.approx(2.0)
    assert reader("batch_fill")(_run()) is None
    assert reader("exec_ms_p50")(_run()) is None
