"""Share of the window, in %, inside the ``exec.dispatch`` phase span, from
its exact total (``rlc_span_seconds{span="exec.dispatch"}``) over the window:
the jitted join call, until it returns answers not yet ready.
Silent where the program has no such span."""

SPAN = "exec.dispatch"


def read(run):
    if not run.window_s or not run.hist_samples("rlc_span_seconds",
                                                span=SPAN):
        return None
    return 100 * run.hist_total("rlc_span_seconds", span=SPAN) / run.window_s
