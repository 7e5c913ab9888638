"""Share of the window the host spent copying chunk results back from
the device (``d2h`` spans)."""
from spanstats import window_share


def read(run):
    return window_share(run.spans, run.tracer_t0, "d2h", run.t0, run.t1)
