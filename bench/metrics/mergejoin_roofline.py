"""Share of its roofline that the ``rlc_mergejoin`` kernel reached.

The least time the join could take is the bytes it must read over the
chip's HBM bandwidth; the kernel's time is the summed device time of its
events in the trace. It is bound by bytes: the join does no
floating-point work, and its compares are far below the VPU's rate.
"""
from bench.lib.peaks import peak

KERNEL = "rlc_mergejoin"


def join_bytes(queries: int, row_len: int) -> int:
    """Bytes one batch of joins must read, whatever implements it: per
    query one out-row and one in-row of ``row_len`` (hub, mr) pairs of
    int32, so 16 bytes per slot."""
    return queries * 16 * row_len


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    secs = tr.seconds_of(KERNEL)
    queries = run.counter_delta("rlc_executor_queries", backend="pallas")
    if secs <= 0 or not queries:
        return None
    least = join_bytes(int(queries), run.row_len) / \
        peak(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / secs
