"""Share of the traced window in which no operation ran on the device,
averaged over the cell's devices."""


def read(run):
    return run.device.idle_share
