"""Own device milliseconds per traced step of the model's backward: ops
that the program's ``model`` scope names inside ``value_and_grad``'s
transpose, a forward recomputed there included (``bench/layers.py``)."""
from bench import layers


def read(run):
    t = layers.times(run)
    return None if t is None else t["backward"]
