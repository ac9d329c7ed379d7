"""Share of the window, in %, inside the ``exec.h2d`` phase span, from
its exact total (``rlc_span_seconds{span="exec.h2d"}``) over the window:
the device join's inputs: the power-of-two pad and the
host-to-device arrays of ``s``, ``t`` and ``mr``.
Silent where the program has no such span."""

SPAN = "exec.h2d"


def read(run):
    if not run.window_s or not run.hist_samples("rlc_span_seconds",
                                                span=SPAN):
        return None
    return 100 * run.hist_total("rlc_span_seconds", span=SPAN) / run.window_s
