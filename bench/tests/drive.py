"""Drive a whole run of a tiny cell on the CPU, past the harness's look for
a chip, with an optional fault planted in the timed path underneath.

  python -m bench.tests.drive <cell> <config> <comm> <chips> <fault> <tmpdir>

Faults: ``none``; ``unchanged`` (the step returns its state unchanged);
``half`` (the step sees half of the batch and averages over it);
``no_exchange`` (the gradient exchange is left out: each chip keeps its own
gradient); ``sum_exchange`` (the exchange sums over chips instead of
averaging).  Prints the run's result as the last line.
"""
import time

T0 = time.perf_counter()

import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "src"))


def plant(fault: str):
    import jax
    from repro.launch import train
    make = train.make_train_step
    if fault == "unchanged":
        def broken(*a, **kw):
            step = make(*a, **kw)

            def same(params, opt_state, batch):
                _, _, m = step(params, opt_state, batch)
                return params, opt_state, m
            return same
        train.make_train_step = broken
    elif fault == "half":
        def broken(*a, **kw):
            step = make(*a, **kw)

            def half(params, opt_state, batch):
                n = batch["tokens"].shape[0] // 2
                return step(params, opt_state, {k: v[:n] for k, v in batch.items()})
            return half
        train.make_train_step = broken
    elif fault == "no_exchange":
        train.sync_grads = lambda grads, comm, axes: grads
    elif fault == "sum_exchange":
        train.sync_grads = lambda grads, comm, axes: jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, axes), grads)
    elif fault != "none":
        raise ValueError(fault)


def main(argv):
    cell, config, comm, chips, fault, tmp = argv
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    from bench import harness, spec
    from bench.tests import tiny
    root = tiny.make_root(Path(tmp), [(cell, config, comm, int(chips))])
    plant(fault)
    result = harness.run(spec.load(root, cell), 2**31 + 7, 0.5, False, t0=T0,
                         require_tpu=False, peaks=tiny.CPU_PEAKS)
    print(json.dumps(result))


if __name__ == "__main__":
    main(sys.argv[1:])
