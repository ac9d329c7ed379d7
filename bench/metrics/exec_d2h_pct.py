"""Share of the window, in %, inside the ``exec.d2h`` phase span, from
its exact total (``rlc_span_seconds{span="exec.d2h"}``) over the window:
the readback of the ready answers to the host.
Silent where the program has no such span."""

SPAN = "exec.d2h"


def read(run):
    if not run.window_s or not run.hist_samples("rlc_span_seconds",
                                                span=SPAN):
        return None
    return 100 * run.hist_total("rlc_span_seconds", span=SPAN) / run.window_s
