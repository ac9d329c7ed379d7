"""Mean time of the delta builder's part of a write over the window
(``rlc_delta_apply_seconds``): the incremental re-derivation of the
index, or the full rebuild it falls back to."""


def read(run):
    samples = run.hist_samples("rlc_delta_apply_seconds")
    if not samples:
        return None
    return 1e3 * sum(samples) / len(samples)
