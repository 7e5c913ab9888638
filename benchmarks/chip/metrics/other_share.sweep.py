"""Share of the window in no leaf span of the program: the sum over the
window's answers of ``timing.phases.other`` (the wall of ``Session.run``
less every mapped span in it) over the window."""


def read(run):
    if not run.done:
        return None
    other = sum(q.report.extras["timing"]["phases"].get("other", 0.0)
                for q in run.done)
    return other / (run.t1 - run.t0)
