"""Set-up seconds: process start to the window's start (host clock).
JAX start, graph data, index build, device layout, compile or cache
load of the cell's shapes, and the warm-up traffic."""


def read(run):
    return run.setup_s
