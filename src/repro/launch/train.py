"""Training launcher.

Runs data-parallel training of any registered architecture on a ``data``
mesh over every visible device, with the paper's communication phase as a
configurable feature:

- ``--comm-mode auto``      gradient averaging by XLA SPMD
- ``--comm-mode explicit``  each device's loss and gradients on its batch
                            shard inside a ``jax.shard_map``, averaged by
                            the bucketed, optionally compressed exchange of
                            ``repro.parallel.grad_sync`` — the step's only
                            gradient reduction

Parameters and optimizer state are replicated, every batch is split on
``data``, and ``train_step`` is compiled once, before the loop: the compiled
step refuses inputs placed any other way instead of recompiling.  A run
reports compile time and count, the median step time and tokens/s (each
step timed from asking for its batch to its loss being ready, the first
step left out), peak device memory, the compiled step's collectives and
Pallas custom calls, the input pipeline's counters (``Prefetcher.stats``)
and the compiled step's instructions per layer (``hlo.layers``).

The loop names its work for the JAX profiler: each step is a
``StepTraceAnnotation("train")`` holding the spans ``train.dispatch`` and
``train.block`` (and ``train.checkpoint``).  ``--profile-dir DIR`` traces
steps 1-3 into ``DIR``; without it the loop starts no profiler.

Examples (CPU, interpret-mode Pallas kernels):
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.train \
      --arch whisper-base --smoke --steps 5 --comm-mode explicit \
      --compression int8
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.train \
      --arch whisper-base --smoke --steps 5 --profile-dir /tmp/prof
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import glob
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs import CommConfig, INPUT_SHAPES, get_config
from repro.data.pipeline import SyntheticLM, Prefetcher, device_put_batch
from repro.models.registry import get_model
from repro.optim.optimizers import get_optimizer
from repro.optim.schedule import clip_by_global_norm, get_schedule
from repro.parallel.grad_sync import make_plan, sync_grads
from repro.utils import hlo

REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory, and
    key its entries on the programs' metadata too.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets no directory.  Otherwise the cache goes to ``<checkout>/.jax_cache``:
    never a temp name, pid or time, since a directory that moves never hits.
    JAX leaves metadata out of the key by default, so a step whose named
    scopes changed would be handed an executable compiled under the old
    ones, and its ``op_name``s, the layers ``hlo.layers`` reads, with it.
    Takes effect only before the process's first compile.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_mesh(devices=None):
    """1-D ``data`` mesh over ``devices`` (default: every visible device).
    Auto axes: XLA SPMD partitions the jitted step, ``shard_map`` takes the
    axis over manually in explicit mode."""
    devices = jax.devices() if devices is None else list(devices)
    return jax.make_mesh((len(devices),), ("data",),
                         axis_types=(AxisType.Auto,), devices=devices)


def make_train_step(api, opt, mesh, comm: CommConfig, lr_fn,
                    clip_norm: float = 0.0):
    """The training step, its parts under the named scopes of
    ``hlo.SCOPES``: ``model`` around the loss (forward, and its transpose,
    the backward), ``optimizer`` around clipping, the learning rate and the
    update, and ``sync_grads``'s own in explicit mode.  Scopes are metadata:
    they name the compiled step's instructions and change none."""
    def loss_fn(params, batch):
        with jax.named_scope(hlo.MODEL):
            return api.loss_fn(params, batch)

    value_and_grad = jax.value_and_grad(loss_fn, has_aux=True)
    loss_and_grads = value_and_grad
    if comm.mode == "explicit":
        def local(params, batch):
            (loss, metrics), grads = value_and_grad(params, batch)
            grads = sync_grads(grads, comm, ("data",))
            return jax.lax.pmean((loss, metrics), "data"), grads

        # check_vma=False: gradients of the replicated params must stay
        # per-device (no implicit psum) until sync_grads averages them, and
        # its all-gather + local reduction leaves them replicated in value,
        # which the varying-axes typing cannot see.
        loss_and_grads = jax.shard_map(local, mesh=mesh,
                                       in_specs=(P(), P("data")),
                                       out_specs=P(), check_vma=False)

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = loss_and_grads(params, batch)
        with jax.named_scope(hlo.OPTIMIZER):
            gnorm = jnp.zeros(())
            if clip_norm > 0:
                grads, gnorm = clip_by_global_norm(grads, clip_norm)
            lr = lr_fn(opt_state.count)
            new_p, new_o = opt.update(params, opt_state, grads, lr)
        return new_p, new_o, {"loss": loss, "grad_norm": gnorm, "lr": lr,
                              **metrics}
    return train_step


def jit_train_step(train_step, mesh):
    """``train_step`` jitted with its placement fixed: params and optimizer
    state replicated, the batch split on ``data``.  Returns the jitted step
    and the (replicated, batch) shardings."""
    repl = NamedSharding(mesh, P())
    split = NamedSharding(mesh, P("data"))
    step = jax.jit(train_step, in_shardings=(repl, repl, split),
                   out_shardings=repl, donate_argnums=(0, 1))
    return step, repl, split


def comm_from_args(args) -> CommConfig:
    """CLI flags -> CommConfig, in one place so the dryrun test and the
    real launcher cannot diverge.  ``scheduler``/``sched_chunks`` select
    the comm-schedule IR order ``sync_grads`` issues its collectives in —
    the same CommPlan the simulator prices, closing the runtime-parity
    gap (the simulator predicting a priority schedule the runtime could
    not execute)."""
    return CommConfig(mode=args.comm_mode, compression=args.compression,
                      fusion_buffer_mb=args.fusion_mb,
                      hierarchical=not args.flat_allreduce,
                      topk_ratio=args.topk_ratio,
                      scheduler=args.scheduler,
                      sched_chunks=args.sched_chunks)


def dryrun(args) -> dict:
    """Build the comm config, bucket plan, and IR order without training.

    What the runtime *would* execute: enough for tests (and operators) to
    check the scheduler wiring end-to-end — CLI flag -> CommConfig ->
    BucketPlan.comm_plan -> bucket order — without touching the data
    pipeline or jit."""
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    comm = comm_from_args(args)
    api = get_model(cfg)
    params = api.init(jax.random.key(args.seed))
    plan, _ = make_plan(params, comm.fusion_buffer_mb)
    order = plan.comm_plan(comm).bucket_order()
    print(f"[dryrun] {cfg.name} | comm={comm.mode} "
          f"scheduler={comm.scheduler}/{comm.sched_chunks} | "
          f"{plan.n_buckets} buckets | issue order: {list(order)}")
    return {"arch": cfg.name, "dryrun": True, "comm_mode": comm.mode,
            "scheduler": comm.scheduler, "sched_chunks": comm.sched_chunks,
            "n_buckets": plan.n_buckets, "bucket_order": list(order)}


class _CompileCounter:
    """Counts backend compiles of one jitted function by name."""

    def __init__(self, fun_name: str):
        self.fun_name, self.count = fun_name, 0

    def __call__(self, event, duration, **kw):
        if (event == "/jax/core/compile/backend_compile_duration"
                and kw.get("fun_name") == self.fun_name):
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


# the steps that ``--profile-dir`` traces: after the first, which compiles
# nothing but pays the first transfers
PROFILE_STEPS = range(1, 4)


def _stop_profile(profile_dir: str) -> str:
    """Stop the profiler; print and return the trace file it wrote."""
    jax.profiler.stop_trace()
    path = max(glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True),
               key=os.path.getmtime)
    print(f"[train] profile of steps {PROFILE_STEPS[0]}-{PROFILE_STEPS[-1]}: {path}")
    return path


def run(args) -> dict:
    if args.dryrun:
        return dryrun(args)
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    shape = INPUT_SHAPES[args.shape].smoke() if args.smoke else INPUT_SHAPES[args.shape]
    if args.batch:
        shape = dataclasses.replace(shape, global_batch=args.batch)
    if args.seq_len:
        shape = dataclasses.replace(shape, seq_len=args.seq_len)

    comm = comm_from_args(args)
    mesh = build_mesh()
    api = get_model(cfg)
    opt = get_optimizer(args.optimizer)
    lr_fn = get_schedule(args.schedule, args.lr, args.warmup, args.steps)
    step_fn, repl, split = jit_train_step(
        make_train_step(api, opt, mesh, comm, lr_fn, clip_norm=args.clip_norm),
        mesh)

    params = jax.device_put(api.init(jax.random.key(args.seed)), repl)
    opt_state = jax.device_put(opt.init(params), repl)
    n_params = sum(int(p.size) for p in jax.tree_util.tree_leaves(params))
    n_buckets = make_plan(params, comm.fusion_buffer_mb)[0].n_buckets
    print(f"[train] {cfg.name} | {n_params/1e6:.1f}M params | "
          f"mesh {dict(mesh.shape)} | comm={comm.mode}/{comm.compression} | "
          f"batch {shape.global_batch} x seq {shape.seq_len}")

    data = SyntheticLM(cfg, shape, seed=args.seed)
    it = Prefetcher((device_put_batch(b, split) for b in data), depth=2)
    tracing, profile = False, None
    try:
        with _CompileCounter("jit(train_step)") as compiles:
            batch = next(it)
            t0 = time.perf_counter()
            compiled = step_fn.lower(params, opt_state, batch).compile()
            t_compile = time.perf_counter() - t0
            losses, times = [], []
            for step in range(args.steps):
                if args.profile_dir and step == PROFILE_STEPS[0]:
                    jax.profiler.start_trace(args.profile_dir)
                    tracing = True
                with jax.profiler.StepTraceAnnotation("train", step_num=step):
                    t0 = time.perf_counter()
                    if step:
                        batch = next(it)
                    with TraceAnnotation("train.dispatch"):
                        params, opt_state, metrics = compiled(params, opt_state,
                                                              batch)
                    with TraceAnnotation("train.block"):
                        jax.block_until_ready(metrics["loss"])
                    dt = time.perf_counter() - t0
                    # step 0 pays one-time costs (first transfers, allocation)
                    if step:
                        times.append(dt)
                    losses.append(float(metrics["loss"]))
                    if step % args.log_every == 0:
                        print(f"  step {step:4d} loss {losses[-1]:.4f} "
                              f"({dt*1e3:.1f} ms)")
                    if args.ckpt_dir and step and step % args.ckpt_every == 0:
                        from repro.checkpoint.store import save
                        with TraceAnnotation("train.checkpoint"):
                            save(args.ckpt_dir,
                                 {"params": params, "opt": opt_state}, step)
                if tracing and step == PROFILE_STEPS[-1]:
                    tracing, profile = False, _stop_profile(args.profile_dir)
    finally:
        if tracing:          # the run ended inside PROFILE_STEPS
            profile = _stop_profile(args.profile_dir)
        it.close()

    text = compiled.as_text()
    mem = compiled.memory_analysis()
    stats = jax.devices()[0].memory_stats() or {}
    tokens_per_step = shape.global_batch * shape.seq_len
    t_step = float(np.median(times)) if times else float("nan")
    result = {
        "arch": cfg.name, "steps": args.steps, "devices": mesh.size,
        "global_batch": shape.global_batch, "seq_len": shape.seq_len,
        "losses": losses, "first_loss": losses[0], "last_loss": losses[-1],
        "median_step_s": t_step, "compile_s": t_compile,
        "compiles": compiles.count,
        "tokens_per_s": tokens_per_step * len(times) / sum(times) if times else 0.0,
        "loss_decreased": losses[-1] < losses[0],
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "temp_bytes": mem.temp_size_in_bytes if mem else None,
        "argument_bytes": mem.argument_size_in_bytes if mem else None,
        "tpu_custom_calls": text.count('custom_call_target="tpu_custom_call"'),
        "collectives": hlo.collective_ops(text),
        "n_buckets": n_buckets,
        "input": it.stats(),
        "layer_ops": dict(collections.Counter(hlo.layers(text).values())),
        "profile": profile,
    }
    print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f}, "
          f"{result['tokens_per_s']:.0f} tok/s "
          f"(median {t_step*1e3:.1f} ms/step, compile {t_compile:.1f} s, "
          f"{compiles.count} compile(s) of train_step)")
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + tiny shape (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=0,
                    help="global batch (default: the shape's)")
    ap.add_argument("--seq-len", type=int, default=0,
                    help="sequence length (default: the shape's)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="cosine", choices=["cosine", "constant"])
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--clip-norm", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--comm-mode", default="auto", choices=["auto", "explicit"])
    ap.add_argument("--compression", default="none",
                    choices=["none", "fp16", "int8", "ternary", "topk"])
    ap.add_argument("--scheduler", default="fifo",
                    choices=["fifo", "priority", "chunked"],
                    help="comm-schedule IR order for explicit grad sync "
                         "(the order the simulator prices)")
    ap.add_argument("--sched-chunks", type=int, default=4,
                    help="chunks per bucket for the pipelined schedulers")
    ap.add_argument("--dryrun", action="store_true",
                    help="build the comm plan and bucket order, skip training")
    ap.add_argument("--fusion-mb", type=float, default=64.0)
    ap.add_argument("--topk-ratio", type=float, default=0.01)
    ap.add_argument("--flat-allreduce", action="store_true")
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--profile-dir", default="",
                    help="trace steps 1-3 with the JAX profiler into this "
                         "directory (device ops, the step's named scopes, "
                         "the input.* and train.* spans)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    return run(args)


if __name__ == "__main__":
    main()
