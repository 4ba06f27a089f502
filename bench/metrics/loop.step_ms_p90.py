"""90th percentile of the window's step wall times, from asking for the
batch to the loss being ready (host clock, the untraced window)."""
import statistics


def read(run):
    if len(run.step_s) < 2:
        return None
    return statistics.quantiles(run.step_s, n=10)[8] * 1e3
