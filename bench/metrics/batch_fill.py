"""Real queries per executed batch over the window
(``rlc_executor_queries`` / ``rlc_executor_batches``)."""


def read(run):
    batches = run.counter_delta("rlc_executor_batches")
    if not batches:
        return None
    return run.counter_delta("rlc_executor_queries") / batches
