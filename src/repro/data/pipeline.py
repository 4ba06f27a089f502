"""Synthetic sharded data pipeline.

Deterministic on (seed, step) so every data-parallel worker can generate its
own shard without coordination — the same property a production loader gets
from sharded file sets.  Provides token batches for LM training, frame/patch
embedding stubs for the audio/VLM frontends, and an infinite iterator with
host-side prefetch.
"""
from __future__ import annotations

import threading
import time
import queue as queue_lib
from typing import Any, Dict, Iterator, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import InputShape, ModelConfig


class SyntheticLM:
    """Zipf-distributed token stream (vocab ranks follow a power law, like
    natural text) with next-token labels."""

    def __init__(self, cfg: ModelConfig, shape: InputShape, seed: int = 0,
                 zipf_a: float = 1.2):
        self.cfg, self.shape, self.seed = cfg, shape, seed
        ranks = np.arange(1, cfg.vocab_size + 1, dtype=np.float64)
        p = ranks ** (-zipf_a)
        self.probs = p / p.sum()

    def batch(self, step: int, batch_size: Optional[int] = None
              ) -> Dict[str, np.ndarray]:
        B = batch_size or self.shape.global_batch
        S = self.shape.seq_len
        rng = np.random.default_rng((self.seed, step))
        stream = rng.choice(self.cfg.vocab_size, size=(B, S + 1), p=self.probs)
        batch = {"tokens": stream[:, :-1].astype(np.int32),
                 "labels": stream[:, 1:].astype(np.int32)}
        if self.cfg.family == "vlm" and self.cfg.prefix_embeds:
            batch["prefix_embeds"] = rng.standard_normal(
                (B, self.cfg.prefix_embeds, self.cfg.d_model)).astype(np.float32)
        if self.cfg.family == "encdec":
            batch["frames"] = rng.standard_normal(
                (B, self.cfg.encoder_seq, self.cfg.d_model)).astype(np.float32)
        return batch

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        step = 0
        while True:
            yield self.batch(step)
            step += 1


_END = object()      # what the source gives once it is exhausted


class _Raised:
    """Queue entry carrying an exception out of the prefetch thread."""

    def __init__(self, exc: BaseException):
        self.exc = exc


class Prefetcher:
    """Host-side prefetch: overlaps next-batch generation with the step.

    An exception raised by the source iterator is re-raised by
    ``__next__``, and an exhausted source ends the iteration, so a failing
    or finished pipeline never leaves the consumer blocked.

    Each side names its work with a profiler span (free when no profiler
    runs) and counts it (``stats``): the producer thread ``input.produce``
    around pulling the next item from the source (for ``device_put_batch``
    over a generator: drawing the batch and starting its copy to the
    device) and ``input.queue_full`` around a ``put`` that found the queue
    full; the consumer ``input.wait`` around its ``get``.
    """

    def __init__(self, it: Iterator, depth: int = 2):
        self.q: queue_lib.Queue = queue_lib.Queue(maxsize=depth)
        self._stop = threading.Event()
        self.produced, self.produce_s, self.queue_full_s = 0, 0.0, 0.0
        self.starved, self.wait_s = 0, 0.0

        def worker():
            try:
                src = iter(it)
                while not self._stop.is_set():
                    t0 = time.perf_counter()
                    with TraceAnnotation("input.produce"):
                        item = next(src, _END)
                    if item is _END:
                        self.q.put(_Raised(StopIteration()))
                        return
                    self.produce_s += time.perf_counter() - t0
                    self.produced += 1
                    try:
                        self.q.put_nowait(item)
                    except queue_lib.Full:
                        t0 = time.perf_counter()
                        with TraceAnnotation("input.queue_full"):
                            self.q.put(item)
                        self.queue_full_s += time.perf_counter() - t0
            except Exception as e:  # handed to the consumer thread
                self.q.put(_Raised(e))

        self.t = threading.Thread(target=worker, daemon=True)
        self.t.start()

    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        with TraceAnnotation("input.wait"):
            try:
                item = self.q.get_nowait()
            except queue_lib.Empty:
                self.starved += 1
                item = self.q.get()
        self.wait_s += time.perf_counter() - t0
        if isinstance(item, _Raised):
            self.q.put(item)         # every later call raises it too
            raise item.exc
        return item

    def stats(self) -> Dict[str, float]:
        """Batches produced, seconds producing them, seconds the producer
        waited on a full queue, gets that found the queue empty, and
        seconds the consumer waited in all."""
        return {"produced": self.produced, "produce_s": self.produce_s,
                "queue_full_s": self.queue_full_s, "starved": self.starved,
                "wait_s": self.wait_s}

    def close(self):
        self._stop.set()
        try:
            while True:
                self.q.get_nowait()
        except queue_lib.Empty:
            pass


def device_put_batch(batch: Dict[str, np.ndarray], sharding: Any):
    """Place a host batch on the mesh, every array split by ``sharding``
    (the leading, batch dimension over the data axis)."""
    return jax.device_put(batch, sharding)
