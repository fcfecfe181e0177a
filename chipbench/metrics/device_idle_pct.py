"""Share of the traced window in which no operation runs on the fullest
device, in %."""


def read(run):
    red = run.trace
    if red is None or red.window_s <= 0:
        return None
    busy = red.busy_ns(red.fullest()) * 1e-9
    return 100.0 * (1.0 - busy / red.window_s)
