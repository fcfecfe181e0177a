"""90th percentile of the window's step times (host clock, each step
ended by ``block_until_ready``), in ms."""
import statistics


def read(run):
    times = run.window.step_times
    if len(times) < 10:
        return None
    return statistics.quantiles(times, n=10, method="inclusive")[-1] * 1e3
