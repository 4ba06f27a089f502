"""One run of one benchmark cell: set-up, three checked steps, the measured
window, an optional traced window, then the comparison with the reference.

The entry that the window drives is the program's own compiled training
step, ``train.jit_train_step(train.make_train_step(...))`` on
``train.build_mesh``, fed by its ``Prefetcher`` over ``device_put_batch``.
The loop is ``train.run``'s: take the next batch, run the step, block on
the loss.  The benchmark makes the weights and the batches from the seed.
"""
from __future__ import annotations

import gc
import itertools
import json
import math
import shutil
import statistics
import sys
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Optional

import numpy as np

from bench import check, flops, spec as spec_lib
from bench.reference.common import leaf_norms, leaf_names
from bench.traffic import generator

TRACE_SECONDS = 2.0      # least device time a traced window holds
TRACE_STEPS = 3          # least steps a traced window holds


class _Compiles:
    """Counts backend compiles, of any function, while it is entered."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def __call__(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def find_devices(chips: int, require_tpu: bool = True):
    """The first ``chips`` devices, or an error message."""
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        return None, (f"needs a TPU; JAX's default backend is "
                      f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        return None, f"needs {chips} chip(s); JAX sees {len(devices)}"
    return devices[:chips], None


class Cell:
    """A cell's compiled step and its state on the device."""

    def __init__(self, cs: spec_lib.CellSpec, devices):
        import jax
        from repro.configs import CommConfig, get_config
        from repro.launch import train
        from repro.models.registry import get_model
        from repro.optim.optimizers import get_optimizer
        from repro.optim.schedule import get_schedule
        from bench import reference, weights

        self.cs, self.devices, self.weights = cs, devices, weights
        c = cs.config
        self.fam = reference.family(c)
        self.cfg = get_config(c["registry"]).replace(**c["overrides"])
        self.api = get_model(self.cfg)
        self.shapes = self.fam.param_shapes(c)
        self.leaf_names = leaf_names(jax.tree_util.tree_map(
            lambda s: 0, self.shapes, is_leaf=_is_shape))
        self._check_layout()
        o = c["optimizer"]
        self.opt = get_optimizer(o["name"], b1=o["b1"], b2=o["b2"],
                                 eps=o["eps"], weight_decay=o["weight_decay"])
        lr_fn = get_schedule(o["schedule"], o["lr"], 0, 0)
        self.mesh = train.build_mesh(devices)
        comm = CommConfig(**cs.traffic["comm"])
        self.step_fn, self.repl, self.split = train.jit_train_step(
            train.make_train_step(self.api, self.opt, self.mesh, comm, lr_fn,
                                  clip_norm=o["clip_norm"]), self.mesh)
        self.rows = cs.traffic["batch_per_chip"] * cs.chips
        self.seq_len = cs.traffic["seq_len"]
        self.frames = ((self.cfg.encoder_seq, self.cfg.d_model)
                       if self.cfg.family == "encdec" else None)
        self._norms = jax.jit(leaf_norms)
        self.compiled = None
        self.marks = {"cell": time.perf_counter()}   # set-up phases, by end time

    def _check_layout(self):
        """The reference's parameter tree and the program's must agree, and
        the program's configuration must hold the file's numbers."""
        import jax
        prog = jax.eval_shape(self.api.init, jax.random.key(0))
        got = dict(zip(leaf_names(prog),
                       (tuple(a.shape) for a in jax.tree_util.tree_leaves(prog))))
        want = dict(zip(self.leaf_names,
                        jax.tree_util.tree_leaves(self.shapes, is_leaf=_is_shape)))
        if got != want:
            raise ValueError(f"parameter layout of {self.cfg.name} differs from "
                             f"the reference's:\n{got}\n{want}")
        for field, value in self.fam.program_fields(self.cs.config).items():
            if getattr(self.cfg, field) != value:
                raise ValueError(f"{self.cfg.name}.{field} = "
                                 f"{getattr(self.cfg, field)!r}, file says {value!r}")

    # -- set-up -------------------------------------------------------------

    def start(self, seed: int):
        """Weights and optimizer state from ``seed``, the batch pool, the
        compiled step (once per process) and the feed."""
        import jax
        from repro.data.pipeline import Prefetcher, device_put_batch
        self.seed = seed
        self.make_params = self.weights.maker(self.shapes, self.cfg.dtype, seed,
                                              self.repl)
        self.params = self.make_params()
        self.opt_state = jax.jit(self.opt.init, out_shardings=self.repl)(self.params)
        jax.block_until_ready(self.opt_state)
        self.marks["weights"] = time.perf_counter()
        self.pool = generator.make_pool(seed, self.cs.traffic, self.cs.chips,
                                        self.cfg.vocab_size, self.frames)
        self.marks["pool"] = time.perf_counter()
        if self.compiled is None:
            batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=self.split)
                     for k, v in self.pool[0].items()}
            self.compiled = self.step_fn.lower(self.params, self.opt_state,
                                               batch).compile()
        self.marks["compile"] = time.perf_counter()
        self.feed = Prefetcher((device_put_batch(b, self.split)
                                for b in itertools.cycle(self.pool)), depth=2)

    def step(self):
        """One step of the window's loop: (seconds waiting for input,
        seconds for the whole step, the step's metrics)."""
        import jax
        t0 = time.perf_counter()
        batch = next(self.feed)
        t1 = time.perf_counter()
        self.params, self.opt_state, m = self.compiled(self.params,
                                                       self.opt_state, batch)
        jax.block_until_ready(m["loss"])
        return t1 - t0, time.perf_counter() - t0, m

    def checked_steps(self, n: int = 3) -> dict:
        """The first ``n`` steps, through the window's own call and feed,
        with what the comparison reads: each step's loss and gradient norm,
        the first step's gradient per leaf as AdamW holds it (mu / (1 - b1)),
        and the change of every leaf over the ``n`` steps."""
        import jax
        import jax.numpy as jnp
        out = {"losses": [], "grad_norms": []}
        b1 = self.cs.config["optimizer"]["b1"]
        for t in range(1, n + 1):
            _, _, m = self.step()
            out["losses"].append(float(m["loss"]))
            out["grad_norms"].append(float(m["grad_norm"]))
            if t == 1:
                out["grad_leaf"] = np.asarray(self._norms(self.opt_state.mu)) / (1 - b1)
        key = self.weights.base_key(self.seed)
        delta = jax.jit(lambda p, k: leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p,
            self.weights.init_tree(k, self.shapes, jnp.dtype(self.cfg.dtype)))))
        out["update_leaf"] = np.asarray(delta(self.params, key))
        return out

    # -- teardown -----------------------------------------------------------

    def stop_feed(self):
        self.feed.close()
        self.feed.t.join(timeout=60)

    def memory_peak(self) -> int:
        stats = [d.memory_stats() or {} for d in self.devices]
        return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    def free(self):
        """Drop the program's state, so that the reference has the chip."""
        self.params = self.opt_state = None
        gc.collect()

    def reference(self, **fault) -> dict:
        from bench import reference
        return reference.train(self.cs.config, self.make_params, self.pool[:3],
                               self.mesh, **fault)


def _trace_window(cell: Cell, n_steps: int, trace_dir: Path) -> dict:
    import jax
    from bench import devtrace
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(n_steps):
                with jax.profiler.TraceAnnotation("bench.input_wait"):
                    batch = next(cell.feed)
                with jax.profiler.TraceAnnotation("bench.step"):
                    cell.params, cell.opt_state, m = cell.compiled(
                        cell.params, cell.opt_state, batch)
                with jax.profiler.TraceAnnotation("bench.block"):
                    jax.block_until_ready(m["loss"])
    finally:
        jax.profiler.stop_trace()
    tr = devtrace.load(str(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return tr


def run(cs: spec_lib.CellSpec, seed: int, seconds: float, trace: bool, *,
        t0: float, require_tpu: bool = True,
        peaks: Optional[dict] = None) -> Optional[dict]:
    """One run; the result line as a dict, or None where the chips are
    missing."""
    devices, err = find_devices(cs.chips, require_tpu)
    if err:
        print(f"bench: {err}", file=sys.stderr)
        return None
    t_devices = time.perf_counter()
    import jax
    dev = devices[0]
    peak = flops.peak(dev.device_kind, peaks)
    from repro.launch import train
    train.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    cell = Cell(cs, devices)
    cell.start(seed)
    prog = cell.checked_steps()
    setup_end = time.perf_counter()
    setup_s = setup_end - t0
    marks = {"devices": t_devices, **cell.marks, "checked_steps": setup_end}
    ends = [t0, *marks.values()]
    print(json.dumps({"setup_phases_s": {k: b - a for k, a, b in
                                         zip(marks, ends, ends[1:])}}), flush=True)

    losses, waits, step_s = [], [], []
    with _Compiles() as compiles:
        start = now = time.perf_counter()
        while now - start < seconds:
            w, s, m = cell.step()
            now = time.perf_counter()
            waits.append(w)
            step_s.append(s)
            losses.append(float(m["loss"]))
        window_s = now - start
    tr = None
    if trace:
        n = max(TRACE_STEPS, math.ceil(TRACE_SECONDS / statistics.mean(step_s)))
        tr = _trace_window(cell, n, cs.root / ".bench_trace")
    cell.stop_feed()
    text = cell.compiled.as_text()
    from repro.utils import hlo
    print(json.dumps({"collectives": hlo.collective_ops(text),
                      "tpu_custom_calls": text.count('custom_call_target="tpu_custom_call"')}),
          flush=True)
    del text
    memory_peak = cell.memory_peak()
    cell.free()

    ref = cell.reference()
    print(json.dumps({"numbers": check.numbers(prog, ref)}), flush=True)
    checks = check.compare(prog, ref, cs.limits)
    checks["window_compiles"] = {"value": compiles.count, "limit": 0}
    failed = sum(1 for x in losses if not math.isfinite(x))
    correct = failed == 0 and check.passed(checks)

    record = SimpleNamespace(
        config=cs.config, traffic=cs.traffic, chips=cs.chips, cfg=cell.cfg,
        setup_s=setup_s, steps=len(step_s), window_s=window_s, waits=waits,
        step_s=step_s, tokens_per_step=cell.rows * cell.seq_len,
        flops_per_step=flops.step(cs.config, cell.rows, cell.seq_len),
        peak=peak, trace=tr)
    wanted = cs.per_layer if trace else cs.end_to_end
    metrics = {}
    for m in wanted:
        value = spec_lib.reader(cs.root, m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": len(step_s),
              "failed": failed, "metrics": metrics, "device": device}
    if tr is not None:
        from bench import devtrace
        per = devtrace.per_device(tr)
        device["busy_s"] = statistics.mean(d["busy"] for d in per.values()) * 1e-9 if per else 0.0
        lo, hi = devtrace.window(tr)
        device["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = devtrace.breakdown(tr)
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    return result


def main(argv, root: Path, t0: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="bench/run.py",
                                 description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (root / "src" / "repro").is_dir():
        print(f"bench: the program (src/repro) is not in {root}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(root / "src"))
    cs = spec_lib.load(root, args.workload)
    result = run(cs, args.seed, args.seconds, bool(args.trace), t0=t0)
    if result is None:
        return 2
    print(json.dumps(result), flush=True)
    return 0
