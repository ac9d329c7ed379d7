"""Share of the window, in %, inside the ``admit`` phase span, from
its exact total (``rlc_span_seconds{span="admit"}``) over the window:
admission of each run of queries between executed batches: parsing,
the control-plane sketch, the cache probe, the scheduler's submit.
Silent where the program has no such span."""

SPAN = "admit"


def read(run):
    if not run.window_s or not run.hist_samples("rlc_span_seconds",
                                                span=SPAN):
        return None
    return 100 * run.hist_total("rlc_span_seconds", span=SPAN) / run.window_s
