"""Own device milliseconds per traced step of the gradient exchange's
bucket copies: ops under the program's ``exchange.pack`` and
``exchange.unpack`` scopes (``bench/layers.py``).  A copy that XLA fused
into a neighbour of another layer is counted in ``step.unattributed_share``
instead."""
from bench import layers


def read(run):
    t = layers.times(run)
    return None if t is None else t["exchange.pack"] + t["exchange.unpack"]
