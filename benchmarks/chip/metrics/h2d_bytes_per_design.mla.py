"""Bytes copied to the device per design: the ``bytes`` of the ``h2d``
spans that start in the window, over the designs the window's queries
evaluated. None where the program has no ``h2d`` spans."""


def read(run):
    designs = sum(q.n_evaluated for q in run.done)
    sent = [e["args"]["bytes"] for e in run.spans if e["name"] == "h2d"
            and run.t0 <= run.tracer_t0 + e["ts"] * 1e-6 <= run.t1]
    if not designs or not sent:
        return None
    return sum(sent) / designs
