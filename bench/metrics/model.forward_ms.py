"""Own device milliseconds per traced step of the model's forward: ops that
the program's ``model`` scope names outside ``value_and_grad``'s transpose
(``bench/layers.py``)."""
from bench import layers


def read(run):
    t = layers.times(run)
    return None if t is None else t["forward"]
