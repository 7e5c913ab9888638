"""Device nanoseconds per design: device-op time in the trace, summed
over the cell's devices, over the designs the window's queries
evaluated."""


def read(run):
    designs = sum(q.n_evaluated for q in run.done)
    if not designs or not run.device.device_s:
        return None
    return run.device.device_s * 1e9 / designs
