"""Own device milliseconds per traced step of the optimizer: ops under the
program's ``optimizer`` scope, gradient clipping, the learning rate and the
update (``bench/layers.py``)."""
from bench import layers


def read(run):
    t = layers.times(run)
    return None if t is None else t["optimizer"]
