"""Share of the window the host spent merging chunk results
(``topk-merge`` spans)."""
from spanstats import window_share


def read(run):
    return window_share(run.spans, run.tracer_t0, "topk-merge", run.t0,
                        run.t1)
