"""Share of the traced window in which no operation ran on a device, in
percent: 1 - (union of the device's operation intervals) / window, the
largest over the cell's devices."""
from bench import devtrace


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    per = devtrace.per_device(run.trace)
    return max(100.0 * (1.0 - d["busy"] / d["window"]) for d in per.values())
