"""Share of the window the host spent in the gene pipeline's ``encode``
spans."""
from spanstats import window_share


def read(run):
    return window_share(run.spans, run.tracer_t0, "encode", run.t0, run.t1)
