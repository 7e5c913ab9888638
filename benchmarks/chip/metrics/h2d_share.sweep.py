"""Share of the window the host spent copying chunk operands to the
device (``h2d`` spans)."""
from spanstats import window_share


def read(run):
    return window_share(run.spans, run.tracer_t0, "h2d", run.t0, run.t1)
