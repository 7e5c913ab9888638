"""Designs evaluated per second: the designs of every query answered in
the window (mapping rows plus joint designs, as each report counts them)
over the time from window start to the last answer."""


def read(run):
    if not run.done:
        return None
    return sum(q.n_evaluated for q in run.done) / (run.t1 - run.t0)
