"""The trace reduction, on a small recorded-layout trace."""
from pathlib import Path

import pytest

from bench.lib import trace as tr

FIXTURE = Path(__file__).parent / "fixtures" / "tpu_window.xplane.pbtxt"
NS = 1e-9


@pytest.fixture(scope="module")
def summary():
    import jax.profiler as jp
    return tr.reduce(jp.ProfileData.from_text_proto(FIXTURE.read_text()))


def test_window_and_devices(summary):
    assert summary.window_s == pytest.approx(10_000 * NS)
    # the Megascale plane is no device
    assert summary.devices == 2


def test_busy_is_the_union_clipped_to_the_window(summary):
    # TPU:0 [1000,1500) + [3500,4700) + [8500,9000); TPU:1 [2000,3000)
    assert summary.busy_s == pytest.approx((2200 + 1000) / 2 * NS)


def test_op_seconds_by_short_name(summary):
    assert summary.op_seconds == pytest.approx({
        "rlc_mergejoin.1": 1500 * NS, "rlc_mergejoin.2": 1000 * NS,
        "compare_reduce_fusion": 300 * NS, "copy.4": 500 * NS})
    assert summary.seconds_of("rlc_mergejoin") == pytest.approx(2500 * NS)
    assert summary.top_ops(1) == [["rlc_mergejoin.1",
                                   pytest.approx(1500 * NS)]]


def test_idle_gaps_named_by_innermost_host_span(summary):
    assert summary.idle_gaps == pytest.approx({
        "bench:call": 6500 * NS, "bench:call+bench:execute": 1900 * NS})
    assert [k for k, _ in summary.top_gaps()] == [
        "bench:call", "bench:call+bench:execute"]


def test_a_trace_without_window_is_refused():
    import jax.profiler as jp
    text = FIXTURE.read_text().replace('"bench:window"', '"other"')
    with pytest.raises(ValueError, match="bench:window"):
        tr.reduce(jp.ProfileData.from_text_proto(text))


@pytest.mark.parametrize("hlo, name", [
    ("%rlc_mergejoin.1 = s32[8,1,128] custom-call(s32[8] %a)",
     "rlc_mergejoin.1"),
    ("%copy.4 = s32[6541,24] copy(%out_hub.1)", "copy.4"),
    ("jit_mergejoin_query(7002215577944237532)",
     "jit_mergejoin_query(7002215577944237532)"),
])
def test_op_name(hlo, name):
    assert tr.op_name(hlo) == name
