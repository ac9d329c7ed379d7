"""What a write costs beyond the delta builder: ``fresh_ms`` less
``delta_build_ms``, the means over the same writes. The freeze patch,
the device relayout and the cache eviction."""


def read(run):
    samples = run.hist_samples("rlc_delta_apply_seconds")
    if not run.write_s or len(samples) != len(run.write_s):
        return None
    return 1e3 * (sum(run.write_s) - sum(samples)) / len(samples)
