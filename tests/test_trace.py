"""What the training step records of itself: the named scopes of its
compiled step and their layer map (``hlo.layers``), the input pipeline's
spans and counters, and ``train.run``'s ``--profile-dir``."""
import glob
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import pytest

from repro.data.pipeline import Prefetcher
from repro.utils import hlo

REPO = Path(__file__).resolve().parent.parent
MODES = ("auto", "explicit")

# the whisper smoke step compiled on 4 virtual devices in both comm modes,
# with its scopes and with ``jax.named_scope`` made a no-op
_COMPILE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import contextlib, json, sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.configs import CommConfig, get_config
from repro.launch import train
from repro.models.registry import get_model
from repro.optim.optimizers import get_optimizer
from repro.optim.schedule import get_schedule

cfg = get_config("whisper-base").smoke()
api, opt = get_model(cfg), get_optimizer("adamw")
mesh = train.build_mesh()


def compiled_text(mode):
    step, repl, split = train.jit_train_step(train.make_train_step(
        api, opt, mesh, CommConfig(mode=mode, fusion_buffer_mb=1.0),
        get_schedule("cosine", 1e-3, 1, 10), clip_norm=1.0), mesh)
    like = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=repl)
    params = jax.tree.map(like, jax.eval_shape(api.init, jax.random.key(0)))
    state = jax.tree.map(like, jax.eval_shape(opt.init, params))
    rows = 2 * mesh.size
    batch = {"tokens": jax.ShapeDtypeStruct((rows, 16), jnp.int32, sharding=split),
             "labels": jax.ShapeDtypeStruct((rows, 16), jnp.int32, sharding=split),
             "frames": jax.ShapeDtypeStruct((rows, cfg.encoder_seq, cfg.d_model),
                                            jnp.float32, sharding=split)}
    return step.lower(params, state, batch).compile().as_text()


out = {mode: {"scoped": compiled_text(mode)} for mode in ("auto", "explicit")}
jax.named_scope = lambda name: contextlib.nullcontext()
for mode in out:
    out[mode]["plain"] = compiled_text(mode)
json.dump(out, open(sys.argv[1], "w"))
"""


@pytest.fixture(scope="module")
def compiled(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("trace")
    script, out = tmp / "compile.py", tmp / "out.json"
    script.write_text(_COMPILE_SCRIPT)
    proc = subprocess.run([sys.executable, str(script), str(out)], cwd=REPO,
                          env=dict(os.environ, PYTHONPATH="src"),
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(out.read_text())


def _instructions(text):
    """Every instruction name of a module's text, by a plain line match."""
    return re.findall(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ", text, re.M)


def _without_metadata(text):
    """The module's text less its ``metadata={...}`` attributes and its
    stack-frame table (``FileNames`` up to the first computation)."""
    out, table = [], False
    for line in text.splitlines():
        table = (table or line == "FileNames") and not line.startswith(("%", "ENTRY"))
        if not table:
            out.append(re.sub(r",? metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


# ---------------------------------------------------------------------------
# scopes and the layer map
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op_name,layer", [
    ("jit(train_step)/jvp(model)/dot_general", "forward"),
    ("jit(train_step)/shard_map/jvp(model)/while/body/mul", "forward"),
    ("jit(train_step)/transpose(jvp(model))/dot_general", "backward"),
    ("jit(train_step)/transpose(jvp(model))/while/body/checkpoint/"
     "rematted_computation/exp", "backward"),
    ("jit(train_step)/shard_map/exchange.pack/concatenate", "exchange.pack"),
    ("jit(train_step)/shard_map/exchange.bucket12/psum", "exchange.bucket"),
    ("jit(train_step)/shard_map/exchange.unpack/convert_element_type",
     "exchange.unpack"),
    ("jit(train_step)/optimizer/sqrt", "optimizer"),
    ("jit(train_step)/shard_map/psum", "none"),
])
def test_scope_layer(op_name, layer):
    assert hlo.scope_layer(op_name) == layer


@pytest.mark.parametrize("mode", MODES)
def test_every_layer_of_the_step_has_instructions(compiled, mode):
    """Forward, backward and optimizer in both modes; the exchange's pack,
    buckets and unpack where the program runs them, in explicit mode."""
    got = set(hlo.layers(compiled[mode]["scoped"]).values())
    assert {"forward", "backward", "optimizer"} <= got
    exchange = {hlo.PACK, hlo.BUCKET, hlo.UNPACK}
    if mode == "explicit":
        assert exchange <= got
    else:
        assert not exchange & got


@pytest.mark.parametrize("mode", MODES)
def test_every_instruction_gets_one_layer(compiled, mode):
    text = compiled[mode]["scoped"]
    names = _instructions(text)
    assert len(names) == len(set(names)) > 1000
    layer_of = hlo.layers(text)
    assert sorted(layer_of) == sorted(names)
    assert set(layer_of.values()) <= set(hlo.LAYERS)


def test_explicit_collectives_are_the_exchanges(compiled):
    """Every payload collective of the explicit step is a bucket's; the
    scalar means of the loss and metrics lie outside every scope."""
    text = compiled["explicit"]["scoped"]
    layer_of = hlo.layers(text)
    payload, scalar = set(), set()
    for comp in hlo.parse_computations(text).values():
        for op in comp.ops:
            if re.sub(r"-(start|done)$", "", op.opcode) in hlo.COLLECTIVE_KINDS:
                rank0 = not re.search(r"\[\d", op.type_str)
                (scalar if rank0 else payload).add(layer_of[op.name])
    assert payload == {hlo.BUCKET}
    assert scalar <= {"none"}


@pytest.mark.parametrize("mode", MODES)
def test_scopes_change_no_instruction(compiled, mode):
    """The optimized module is the same with and without the scopes, but
    for metadata and the stack-frame table."""
    scoped, plain = compiled[mode]["scoped"], compiled[mode]["plain"]
    assert "optimizer/" in scoped and "optimizer/" not in plain
    assert _without_metadata(scoped) == _without_metadata(plain)


_CACHE_SCRIPT = r"""
import json, sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.launch import train
train.enable_compile_cache()


def step(x, scope):
    with jax.named_scope(scope):
        return jnp.sin(x) * 2


texts = [jax.jit(lambda x: step(x, s)).lower(jnp.ones(3)).compile().as_text()
         for s in ("before", "after")]
print(json.dumps({"after": "after/" in texts[1], "before": "before/" in texts[1]}))
"""


def test_the_compile_cache_keeps_apart_steps_that_differ_in_scopes(tmp_path):
    """Two programs that differ only in their scopes compile apart: JAX's
    default key leaves metadata out, and would hand the second the first's
    executable, ``op_name``s and all."""
    script = tmp_path / "cache.py"
    script.write_text(_CACHE_SCRIPT)
    env = dict(os.environ, PYTHONPATH="src",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    proc = subprocess.run([sys.executable, str(script)], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == {
        "after": True, "before": False}


# ---------------------------------------------------------------------------
# the input pipeline
# ---------------------------------------------------------------------------

def test_prefetcher_counts_a_slow_source_and_starved_gets():
    def slow():
        for i in range(4):
            time.sleep(0.05)
            yield i

    it = Prefetcher(slow(), depth=2)
    assert list(it) == [0, 1, 2, 3]
    s = it.stats()
    assert s["produced"] == 4
    assert s["produce_s"] >= 4 * 0.05
    assert s["starved"] >= 3            # each item came later than its get
    assert 0.1 <= s["wait_s"] <= s["produce_s"] + 0.1
    assert s["queue_full_s"] == 0


def test_prefetcher_counts_the_wait_on_a_full_queue():
    it = Prefetcher(iter(range(10)), depth=1)
    time.sleep(0.1)                     # the producer fills the queue
    for _ in range(3):
        next(it)
        time.sleep(0.05)
    it.close()
    it.t.join(timeout=10)
    s = it.stats()
    assert s["queue_full_s"] >= 0.1
    assert not it.t.is_alive()


# ---------------------------------------------------------------------------
# train.run: counters, layers and --profile-dir
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    from repro.launch import train
    out = tmp_path_factory.mktemp("profile")
    res = train.main(["--arch", "whisper-base", "--smoke", "--steps", "5",
                      "--comm-mode", "explicit", "--log-every", "100",
                      "--profile-dir", str(out)])
    return res, out


def test_run_reports_input_counters_and_layer_ops(profiled):
    res, _ = profiled
    assert res["input"]["produced"] >= 5
    assert res["input"]["wait_s"] >= 0 and res["input"]["produce_s"] > 0
    ops = res["layer_ops"]
    assert set(ops) <= set(hlo.LAYERS)
    assert all(ops.get(k, 0) > 0 for k in ("forward", "backward", hlo.PACK,
                                            hlo.UNPACK, hlo.OPTIMIZER))
    assert res["median_step_s"] > 0 and res["tokens_per_s"] > 0


def test_profile_dir_holds_the_spans_of_three_steps(profiled):
    from jax.profiler import ProfileData
    res, out = profiled
    paths = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
    assert paths and res["profile"] == paths[0]
    names = [e.name for plane in ProfileData.from_file(paths[0]).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events]
    for span in ("input.produce", "input.wait", "train.dispatch", "train.block"):
        assert names.count(span) >= 3, span
    assert names.count("train") == 3          # steps 1-3


def test_no_profiler_without_the_flag(monkeypatch):
    """Without ``--profile-dir`` the loop starts no profiler."""
    import jax
    from repro.launch import train
    started = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: started.append(a))
    train.main(["--arch", "whisper-base", "--smoke", "--steps", "2",
                "--log-every", "100"])
    assert not started
