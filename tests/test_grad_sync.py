"""Bucketed grad-sync: packing plan properties (hypothesis), single-device
semantics, real multi-device collective semantics in a subprocess with 8
fake XLA devices, and the data-parallel train step on 4 of them."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from jax.sharding import PartitionSpec as P

from repro.configs.base import CommConfig
from repro.parallel.grad_sync import (BucketPlan, exchange_collectives,
                                      make_plan, pack, sync_grads, unpack)

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# packing plan
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(sizes=st.lists(st.integers(1, 10_000), min_size=1, max_size=40),
       limit_kb=st.integers(1, 256))
def test_plan_respects_limit_and_covers(sizes, limit_kb):
    shapes = [(s,) for s in sizes]
    plan = BucketPlan(shapes, [jnp.float32] * len(shapes), limit_kb * 1024)
    assert sum(plan.bucket_sizes) == sum(sizes)
    # no bucket exceeds the limit unless a single tensor does
    limit_elems = limit_kb * 1024 // 4
    for b, bsize in enumerate(plan.bucket_sizes):
        members = [s for s, (bb, _) in zip(plan.sizes, plan.assignments)
                   if bb == b]
        assert bsize <= max(limit_elems, max(members))
    # offsets are consistent
    for (b, off), size in zip(plan.assignments, plan.sizes):
        assert off + size <= plan.bucket_sizes[b]


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000), limit_kb=st.integers(1, 64))
def test_pack_unpack_roundtrip(seed, limit_kb):
    rng = np.random.default_rng(seed)
    tree = {"a": jnp.asarray(rng.standard_normal((rng.integers(1, 50),
                                                  rng.integers(1, 50)))),
            "b": [jnp.asarray(rng.standard_normal(int(rng.integers(1, 999))),
                              dtype=jnp.float32),
                  jnp.asarray(rng.standard_normal(1).astype(np.float32))[0]]}
    plan, treedef = make_plan(tree, limit_kb / 1024.0)
    leaves = jax.tree_util.tree_leaves(tree)
    out = unpack(plan, pack(plan, leaves))
    for a, b in zip(out, leaves):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=1e-6)


# ---------------------------------------------------------------------------
# single-device semantics (collectives degenerate to identity/mean-of-one)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("compression", ["none", "fp16", "int8"])
def test_sync_identity_on_one_device(compression):
    mesh = jax.make_mesh((1,), ("data",))
    grads = {"w": jnp.arange(300, dtype=jnp.float32).reshape(20, 15) / 300.0,
             "b": jnp.ones((7,), jnp.bfloat16)}
    comm = CommConfig(compression=compression, hierarchical=False)
    out = jax.shard_map(lambda g: sync_grads(g, comm, ("data",)), mesh=mesh,
                        in_specs=P(), out_specs=P(), check_vma=False)(grads)
    tol = {"none": 1e-7, "fp16": 1e-2, "int8": 1e-2}[compression]
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(grads)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=tol)


# ---------------------------------------------------------------------------
# multi-device semantics (8 fake devices, subprocess)
# ---------------------------------------------------------------------------

_MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import functools, json, sys
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
sys.path.insert(0, "src")
from repro.configs.base import CommConfig
from repro.parallel.grad_sync import sync_grads

mesh = jax.make_mesh((2, 4), ("pod", "data"))
results = {}
for compression, hier in [("none", False), ("none", True), ("fp16", False),
                          ("int8", False), ("ternary", False)]:
    # per-device distinct gradients; the sync must produce their mean
    def make(shape, seed):
        vals = [jax.random.normal(jax.random.key(seed + i), shape)
                for i in range(8)]
        stacked = jnp.stack(vals)          # (8, ...)
        arr = jax.device_put(
            stacked.reshape(2, 4, *shape),
            NamedSharding(mesh, P("pod", "data")))
        return vals, arr

    vals_w, w = make((16, 8), 0)
    vals_b, b = make((40,), 100)
    expect_w = np.mean([np.asarray(v) for v in vals_w], axis=0)
    expect_b = np.mean([np.asarray(v) for v in vals_b], axis=0)

    comm = CommConfig(compression=compression, hierarchical=hier)
    # each device holds its own gradients: a (1, 1, ...) block of the
    # (2, 4, ...)-stacked tree; the sync must leave every block the mean
    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P("pod", "data"), P("pod", "data")),
                       out_specs=(P("pod", "data"), P("pod", "data")),
                       check_vma=False)
    def run(wb, bb):
        out = sync_grads({"w": wb[0, 0], "b": bb[0, 0]}, comm,
                         ("pod", "data"))
        return out["w"][None, None], out["b"][None, None]

    out_w, out_b = run(w, b)
    got_w = np.asarray(out_w)            # every device's block
    got_b = np.asarray(out_b)
    err_w = float(np.abs(got_w - expect_w).max())
    err_b = float(np.abs(got_b - expect_b).max())
    results[f"{compression}/{'hier' if hier else 'flat'}"] = [err_w, err_b]
print(json.dumps(results))
"""


@pytest.mark.slow
def test_multidevice_mean_semantics(tmp_path):
    script = tmp_path / "multidev.py"
    script.write_text(_MULTIDEV_SCRIPT)
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, str(script)], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    results = json.loads(proc.stdout.strip().splitlines()[-1])
    # exact for none, small for fp16/int8, bounded for ternary
    tol = {"none/flat": 1e-6, "none/hier": 1e-6, "fp16/flat": 2e-2,
           "int8/flat": 2e-2, "ternary/flat": 1.5}
    for k, (ew, eb) in results.items():
        assert ew <= tol[k] and eb <= tol[k], (k, ew, eb)


# ---------------------------------------------------------------------------
# the data-parallel train step on 4 devices (subprocess)
# ---------------------------------------------------------------------------

_TRAIN_4DEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
sys.path.insert(0, "src")
from repro.launch import train

out = {}
for mode in ("auto", "explicit"):
    r = train.main(["--arch", "whisper-base", "--smoke", "--steps", "2",
                    "--batch", "8", "--fusion-mb", "1", "--warmup", "0",
                    "--lr", "1e-2", "--log-every", "100",
                    "--comm-mode", mode])
    out[mode] = {k: r[k] for k in ("losses", "collectives", "n_buckets",
                                   "devices", "compiles")}
print(json.dumps(out))
"""


@pytest.mark.slow
def test_explicit_step_matches_auto_on_four_devices(tmp_path):
    """Explicit mode's per-device loss/grad + bucketed exchange matches XLA
    SPMD data parallelism, and is the compiled step's only gradient
    reduction: no collective the SPMD partitioner inserted, and exactly the
    bucket plan's exchange collectives."""
    script = tmp_path / "train4.py"
    script.write_text(_TRAIN_4DEV_SCRIPT)
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, str(script)], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    auto, exp = out["auto"], out["explicit"]
    assert auto["devices"] == exp["devices"] == 4
    assert auto["compiles"] == exp["compiles"] == 1
    # a step with lr 1e-2 moves the loss, so step 1 checks the update too
    np.testing.assert_allclose(exp["losses"], auto["losses"], rtol=1e-5)
    assert auto["collectives"]["spmd"], "auto mode: XLA reduces the grads"
    nb = exp["n_buckets"]
    assert nb > 1
    assert exp["collectives"]["spmd"] == {}
    assert exp["collectives"]["exchange"] == {"reduce-scatter": nb,
                                              "all-gather": nb}
    assert (sum(exp["collectives"]["exchange"].values())
            == exchange_collectives(CommConfig(), nb))
