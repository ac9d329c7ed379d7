"""Median wall time of one executed batch over the window
(``rlc_executor_batch_seconds``)."""
import numpy as np


def read(run):
    xs = run.hist_samples("rlc_executor_batch_seconds")
    return float(np.median(xs)) * 1e3 if xs else None
