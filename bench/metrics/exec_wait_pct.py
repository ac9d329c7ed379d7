"""Share of the window, in %, inside the ``exec.wait`` phase span, from
its exact total (``rlc_span_seconds{span="exec.wait"}``) over the window:
the wait until the join's answers are ready on the device.
Silent where the program has no such span."""

SPAN = "exec.wait"


def read(run):
    if not run.window_s or not run.hist_samples("rlc_span_seconds",
                                                span=SPAN):
        return None
    return 100 * run.hist_total("rlc_span_seconds", span=SPAN) / run.window_s
