"""Host index build of the cell's graph, timed by the benchmark around
``build_rlc_index_with_stats`` with the configured build backend."""


def read(run):
    return run.index_s
