"""Set-up seconds: process start to window start (imports, device
initialisation, compile or compile-cache load, warm-up)."""


def read(run):
    return run.setup_s
