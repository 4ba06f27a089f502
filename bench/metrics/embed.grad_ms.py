"""Own device milliseconds per traced step of the embedding's gradient: ops
under the program's ``embed.grad`` scope, the float32 sum of the token
rows' cotangents and its rounding to the table's dtype
(``bench/layers.py``).  None for a program without that scope."""
from bench import layers


def read(run):
    t = layers.times(run)
    return None if t is None else t.get("embed.grad")
