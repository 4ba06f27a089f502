"""Share of the step's own device time, in percent, in ops of no single
layer: fusions of several (``mixed``) and ops under none of the program's
scopes (``none``), over the own time of every op (``bench/layers.py``)."""
from bench import layers


def read(run):
    t = layers.times(run)
    if t is None or not sum(t.values()):
        return None
    return 100.0 * sum(t[k] for k in layers.UNATTRIBUTED) / sum(t.values())
