"""Seeded training batches, the one generator every traffic file feeds.

The arithmetic follows the program's ``SyntheticLM``: token ids drawn from a
Zipf law over the vocabulary (rank r has weight r**-zipf_a), labels the next
token of the same stream, and, for an encoder-decoder model, frame
embeddings as standard normal float32.  It draws a pool of distinct global
batches once, in set-up; the run cycles through them.  Batch ``i`` of seed
``s`` is the same on every run, whatever the number of threads: each row of
frames has its own stream, keyed by (s, i, row).

A traffic file holds: ``batch_per_chip``, ``seq_len``, ``zipf_a``, ``pool``
(distinct batches, at least 3, so the three checked steps see rows that all
differ) and ``comm`` (the program's ``CommConfig`` fields for the cell).
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

import numpy as np


def zipf_probs(vocab: int, a: float) -> np.ndarray:
    p = np.arange(1, vocab + 1, dtype=np.float64) ** (-a)
    return p / p.sum()


def make_pool(seed: int, traffic: dict, chips: int, vocab: int,
              frames: Optional[tuple] = None) -> List[Dict[str, np.ndarray]]:
    """``traffic['pool']`` global batches of ``batch_per_chip * chips`` rows.
    ``frames``: (n_frames, width) of the encoder input, or None."""
    B = traffic["batch_per_chip"] * chips
    S = traffic["seq_len"]
    probs = zipf_probs(vocab, traffic["zipf_a"])
    pool = []
    for i in range(traffic["pool"]):
        rng = np.random.default_rng([seed, i])
        stream = rng.choice(vocab, size=(B, S + 1), p=probs)
        batch = {"tokens": stream[:, :-1].astype(np.int32),
                 "labels": stream[:, 1:].astype(np.int32)}
        if frames is not None:
            out = np.empty((B, *frames), np.float32)

            def fill(r, i=i, out=out):
                np.random.default_rng([seed, i, r]).standard_normal(
                    out=out[r], dtype=np.float32)

            with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as ex:
                list(ex.map(fill, range(B)))
            batch["frames"] = out
        pool.append(batch)
    return pool
