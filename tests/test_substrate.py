"""Substrate tests: data pipeline determinism, checkpoint roundtrip,
optimizers descend, train/serve launchers run end-to-end."""
import json
import math
import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import INPUT_SHAPES, get_config
from repro.data.pipeline import Prefetcher, SyntheticLM

# interpret-mode Pallas / full-model tests: minutes of wall clock on CPU
pytestmark = pytest.mark.slow

REPO = Path(__file__).resolve().parent.parent



# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def test_data_deterministic_per_step():
    cfg = get_config("stablelm-3b").smoke()
    shape = INPUT_SHAPES["train_4k"].smoke()
    a = SyntheticLM(cfg, shape, seed=7).batch(3)
    b = SyntheticLM(cfg, shape, seed=7).batch(3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = SyntheticLM(cfg, shape, seed=8).batch(3)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_data_labels_are_shifted_stream():
    cfg = get_config("stablelm-3b").smoke()
    shape = INPUT_SHAPES["train_4k"].smoke()
    b = SyntheticLM(cfg, shape).batch(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])
    assert b["tokens"].max() < cfg.vocab_size


def test_data_frontend_stubs():
    vlm = get_config("internvl2-2b").smoke()
    b = SyntheticLM(vlm, INPUT_SHAPES["train_4k"].smoke()).batch(0)
    assert b["prefix_embeds"].shape == (2, vlm.prefix_embeds, vlm.d_model)
    enc = get_config("whisper-base").smoke()
    b = SyntheticLM(enc, INPUT_SHAPES["train_4k"].smoke()).batch(0)
    assert b["frames"].shape == (2, enc.encoder_seq, enc.d_model)


def test_prefetcher():
    cfg = get_config("stablelm-3b").smoke()
    shape = INPUT_SHAPES["train_4k"].smoke()
    it = Prefetcher(iter(SyntheticLM(cfg, shape)), depth=2)
    b0, b1 = next(it), next(it)
    assert not np.array_equal(b0["tokens"], b1["tokens"])
    it.close()


def _next_within(it, seconds=10.0):
    """``next(it)`` in a helper thread, so a hang fails instead of blocking."""
    with ThreadPoolExecutor(1) as ex:
        return ex.submit(next, it).result(timeout=seconds)


def test_prefetcher_reraises_source_error():
    def source():
        yield 1
        raise ValueError("bad shard")

    it = Prefetcher(source(), depth=2)
    assert _next_within(it) == 1
    for _ in range(2):                     # and on every later call
        with pytest.raises(ValueError, match="bad shard"):
            _next_within(it)


def test_prefetcher_stops_with_source():
    it = Prefetcher(iter([1, 2, 3]), depth=2)
    assert [_next_within(it) for _ in range(3)] == [1, 2, 3]
    with pytest.raises(StopIteration):
        _next_within(it)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint.store import latest_step, restore, save
    tree = {"w": jnp.arange(6.0).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.bfloat16),
                       "c": jnp.asarray(3)}}
    save(tmp_path, tree, step=10)
    save(tmp_path, jax.tree_util.tree_map(lambda x: x * 0, tree), step=20)
    assert latest_step(tmp_path) == 20
    r10 = restore(tmp_path, tree, step=10)
    for a, b in zip(jax.tree_util.tree_leaves(r10),
                    jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
        assert a.dtype == b.dtype


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    from repro.checkpoint.store import restore, save
    save(tmp_path, {"w": jnp.ones((2, 3))}, step=1)
    with pytest.raises(ValueError):
        restore(tmp_path, {"w": jnp.ones((3, 2))})


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["sgd", "momentum", "adamw"])
def test_optimizer_descends_quadratic(name):
    from repro.optim.optimizers import get_optimizer
    opt = get_optimizer(name)
    params = {"x": jnp.asarray([3.0, -2.0])}
    state = opt.init(params)
    loss = lambda p: jnp.sum(jnp.square(p["x"]))
    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state = opt.update(params, state, g, 0.05)
    assert float(loss(params)) < 0.05


def test_adamw_moments_are_f32():
    from repro.optim.optimizers import get_optimizer
    opt = get_optimizer("adamw")
    params = {"w": jnp.ones((4, 4), jnp.bfloat16)}
    state = opt.init(params)
    assert state.mu["w"].dtype == jnp.float32
    assert state.nu["w"].dtype == jnp.float32


# ---------------------------------------------------------------------------
# launchers (integration)
# ---------------------------------------------------------------------------

def test_train_loss_decreases():
    from repro.launch import train as train_mod
    res = train_mod.main(["--arch", "stablelm-3b", "--smoke", "--steps", "8",
                          "--log-every", "100"])
    assert res["loss_decreased"], res


def test_train_explicit_comm_matches_auto():
    from repro.launch import train as train_mod
    r_auto = train_mod.main(["--arch", "stablelm-3b", "--smoke", "--steps",
                             "5", "--comm-mode", "auto", "--log-every", "100"])
    r_exp = train_mod.main(["--arch", "stablelm-3b", "--smoke", "--steps",
                            "5", "--comm-mode", "explicit", "--log-every",
                            "100"])
    # on one device explicit sync is a no-op: identical loss trajectories
    assert abs(r_auto["last_loss"] - r_exp["last_loss"]) < 1e-4


def test_train_compiles_step_once():
    """Placed inputs keep step 1's argument types equal to step 0's, so the
    step is compiled once per run; ``--seq-len`` overrides the shape."""
    from repro.launch import train as train_mod
    res = train_mod.main(["--arch", "whisper-base", "--smoke", "--steps", "3",
                          "--seq-len", "32", "--comm-mode", "explicit",
                          "--log-every", "100"])
    assert res["compiles"] == 1, res
    assert res["seq_len"] == 32 and len(res["losses"]) == 3
    assert all(math.isfinite(x) for x in res["losses"])


_CACHE_SCRIPT = r"""
import json, sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.launch import train
path = train.enable_compile_cache()
if sys.argv[1] == "compile":
    jax.jit(lambda x: x * 2)(jnp.ones(3)).block_until_ready()
print(json.dumps({"returned": path,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


@pytest.mark.parametrize("env_dir", [False, True], ids=["default", "env"])
def test_compile_cache_dir(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and is written to; otherwise the cache
    is the fixed directory in the checkout."""
    script = tmp_path / "cache.py"
    script.write_text(_CACHE_SCRIPT)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = "src"
    cache = tmp_path / "cache"
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    proc = subprocess.run(
        [sys.executable, str(script), "compile" if env_dir else "none"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    want = str(cache) if env_dir else str(REPO / ".jax_cache")
    assert out["returned"] == out["config"] == want
    if env_dir:
        assert any(cache.iterdir()), "the compile was not cached there"


def test_serve_generates():
    from repro.launch import serve as serve_mod
    res = serve_mod.main(["--arch", "stablelm-3b", "--smoke", "--batch", "2",
                          "--prompt-len", "16", "--gen", "4"])
    assert res["decode_tok_per_s"] > 0


# ---------------------------------------------------------------------------
# flops model sanity
# ---------------------------------------------------------------------------

def test_param_count_matches_init():
    """Analytic per-layer params within 10% of the real initialized tree
    (analytic model skips norms/padding; both are sub-percent at scale)."""
    from repro.core.flops import param_count
    from repro.models.registry import get_model
    for arch in ("stablelm-3b", "rwkv6-1.6b", "moonshot-v1-16b-a3b"):
        cfg = get_config(arch).smoke()
        api = get_model(cfg)
        p = jax.eval_shape(api.init, jax.random.key(0))
        real = sum(int(l.size) for l in jax.tree_util.tree_leaves(p))
        analytic = param_count(cfg)
        assert abs(real - analytic) / real < 0.10, (arch, real, analytic)


def test_model_flops_scaling():
    from repro.core.flops import model_flops
    cfg = get_config("deepseek-coder-33b")
    t = model_flops(cfg, INPUT_SHAPES["train_4k"])
    p = model_flops(cfg, INPUT_SHAPES["prefill_32k"])
    # train = 6ND vs prefill = 2ND at equal token counts
    assert t / p == pytest.approx(3.0, rel=0.01)
    d = model_flops(cfg, INPUT_SHAPES["decode_32k"])
    assert d < p / 1000     # one token vs 32k tokens


def test_moe_active_params():
    from repro.core.flops import active_param_count, param_count
    cfg = get_config("arctic-480b")
    assert active_param_count(cfg) < 0.2 * param_count(cfg)


# ---------------------------------------------------------------------------
# schedules / clipping
# ---------------------------------------------------------------------------

def test_warmup_cosine_schedule():
    from repro.optim.schedule import warmup_cosine
    lr = warmup_cosine(1e-3, warmup_steps=10, total_steps=100)
    assert float(lr(0)) == 0.0
    assert float(lr(10)) == pytest.approx(1e-3, rel=1e-5)
    assert float(lr(5)) == pytest.approx(5e-4, rel=1e-5)
    assert float(lr(100)) == pytest.approx(1e-4, rel=1e-3)   # final_frac
    # monotone decay after warmup
    vals = [float(lr(s)) for s in range(10, 101, 10)]
    assert all(a >= b for a, b in zip(vals, vals[1:]))


def test_clip_by_global_norm():
    from repro.optim.schedule import clip_by_global_norm, global_norm
    g = {"a": jnp.full((4,), 3.0), "b": jnp.full((4,), 4.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(10.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
    # under the limit: untouched
    same, _ = clip_by_global_norm(g, 100.0)
    np.testing.assert_allclose(np.asarray(same["a"]), np.asarray(g["a"]))
