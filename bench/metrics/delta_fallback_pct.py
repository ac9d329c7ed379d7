"""Share of the window's delta applies, in %, that the delta builder
abandoned to a full rebuild (``rlc_delta_fallbacks`` over
``rlc_delta_applies``)."""


def read(run):
    applies = run.counter_delta("rlc_delta_applies")
    if not applies:
        return None
    return 100 * run.counter_delta("rlc_delta_fallbacks") / applies
