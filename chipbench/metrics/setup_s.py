"""Process start to the first step of the window: imports, weights made on
the device, compile or compile-cache load, and the checked steps."""


def read(run):
    return run.setup_s
