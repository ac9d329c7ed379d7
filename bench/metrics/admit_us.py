"""Host microseconds per query outside the executor: the window less the
executor's batch seconds (``rlc_executor_batch_seconds``), over the
queries answered. Parsing, the cache probe, the scheduler and the
caller's own loop."""


def read(run):
    if not run.answered:
        return None
    exec_s = run.hist_total("rlc_executor_batch_seconds")
    return (run.window_s - exec_s) / run.answered * 1e6
