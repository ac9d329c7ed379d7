"""Share of the window, in %, inside the ``answer`` phase span, from
its exact total (``rlc_span_seconds{span="answer"}``) over the window:
the controller update and the fan-out of each batch's answers
to the cache and the callers.
Silent where the program has no such span."""

SPAN = "answer"


def read(run):
    if not run.window_s or not run.hist_samples("rlc_span_seconds",
                                                span=SPAN):
        return None
    return 100 * run.hist_total("rlc_span_seconds", span=SPAN) / run.window_s
