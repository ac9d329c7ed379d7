"""Freeze and device layout of the built index: ``RLCService.build``
over the index, until every device array is ready (host clock)."""


def read(run):
    return run.layout_s
