"""Queries answered in the window over its wall time less the writes'
(host clock): the query rate on each write's new layout and emptied
cache. It stands beside ``fresh_ms``, so that a write path that moves
its cost into the calls after it shows."""


def read(run):
    if not run.write_s:
        return None
    rest = run.window_s - sum(run.write_s)
    return run.answered / rest if rest > 0 else None
