"""Seconds from process start to the first timed step: imports, weights,
batch pool, compile (or its load from the cache) and the checked steps."""


def read(run):
    return run.setup_s
