"""The step's device time per layer (``bench/layers.py`` and its readers),
on a hand-made compiled module and trace, and on a recorded chip slice."""
import json
import re
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "src"))

from bench import devtrace, layers, spec  # noqa: E402
from repro.utils import hlo  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
READERS = ("model.forward_ms", "model.backward_ms", "exchange.pack_unpack_ms",
           "optimizer.update_ms", "step.unattributed_share")

# a compiled step in miniature: forward and backward ops, a backward loop,
# the three exchange scopes, the optimizer, a fusion of unpack and
# optimizer, and a copy XLA made (no metadata)
HLO = """HloModule jit_train_step, is_scheduled=true

%fused_unpack_adam (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0), metadata={op_name="params['w']"}
  %constant.1 = f32[] constant(1), metadata={op_name="jit(train_step)"}
  %broadcast.1 = f32[8]{0} broadcast(%constant.1), dimensions={}, metadata={op_name="broadcast.7"}
  %convert.1 = f32[8]{0} convert(%param_0), metadata={op_name="jit(train_step)/exchange.unpack/convert_element_type"}
  ROOT %multiply.1 = f32[8]{0} multiply(%convert.1, %broadcast.1), metadata={op_name="jit(train_step)/optimizer/mul"}
}

%fused_adam (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %constant.2 = f32[] constant(2), metadata={op_name="jit(train_step)"}
  %broadcast.2 = f32[8]{0} broadcast(%constant.2), dimensions={}, metadata={op_name="jit(train_step)/shard_map/broadcast.9"}
  ROOT %add.2 = f32[8]{0} add(%param_0.1, %broadcast.2), metadata={op_name="jit(train_step)/optimizer/add"}
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%p), index=0
  %gte.1 = f32[8]{0} get-tuple-element(%p), index=1
  %dot.9 = f32[8]{0} dot(%gte.1, %gte.1), metadata={op_name="jit(train_step)/transpose(jvp(model))/while/body/dot_general"}
  %exp.9 = f32[8]{0} exponential(%dot.9), metadata={op_name="jit(train_step)/transpose(jvp(model))/while/body/checkpoint/rematted_computation/exp"}
  ROOT %tuple.9 = (s32[], f32[8]{0}) tuple(%gte.0, %exp.9)
}

%cond (p.1: (s32[], f32[8])) -> pred[] {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  %gte.2 = s32[] get-tuple-element(%p.1), index=0
  %constant.3 = s32[] constant(6)
  ROOT %compare.3 = pred[] compare(%gte.2, %constant.3), direction=LT, metadata={op_name="jit(train_step)/transpose(jvp(model))/while/cond/lt"}
}

ENTRY %main (param.1: f32[8]) -> f32[8] {
  %param.1 = f32[8]{0} parameter(0), metadata={op_name="params['w']"}
  %dot.1 = f32[8]{0} dot(%param.1, %param.1), metadata={op_name="jit(train_step)/jvp(model)/dot_general"}
  %tuple.1 = (s32[], f32[8]{0}) tuple(%dot.1, %dot.1)
  %while.1 = (s32[], f32[8]{0}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(train_step)/transpose(jvp(model))/while"}
  %gte.3 = f32[8]{0} get-tuple-element(%while.1), index=1
  %concatenate.1 = f32[8]{0} concatenate(%gte.3), dimensions={0}, metadata={op_name="jit(train_step)/shard_map/exchange.pack/concatenate"}
  %all-reduce.1 = f32[8]{0} all-reduce(%concatenate.1), replica_groups={}, metadata={op_name="jit(train_step)/shard_map/exchange.bucket0/psum"}
  %all-reduce.2 = f32[8]{0} all-reduce(%all-reduce.1), replica_groups={}, metadata={op_name="jit(train_step)/shard_map/exchange.bucket1/psum"}
  %slice.1 = f32[8]{0} slice(%all-reduce.2), slice={[0:8]}, metadata={op_name="jit(train_step)/shard_map/exchange.unpack/slice"}
  %fusion.1 = f32[8]{0} fusion(%slice.1), kind=kLoop, calls=%fused_unpack_adam, metadata={op_name="jit(train_step)/optimizer/mul"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_adam
  %copy.1 = f32[8]{0} copy(%fusion.2)
  ROOT %multiply.2 = f32[8]{0} multiply(%copy.1, %copy.1), metadata={op_name="jit(train_step)/optimizer/mul"}
}
"""

LAYER = {"dot.1": "forward", "while.1": "backward", "dot.9": "backward",
         "exp.9": "backward", "compare.3": "backward",
         "concatenate.1": "exchange.pack", "all-reduce.1": "exchange.bucket",
         "all-reduce.2": "exchange.bucket", "slice.1": "exchange.unpack",
         "fusion.1": "mixed", "fusion.2": "optimizer", "copy.1": "none",
         "multiply.2": "optimizer", "param.1": "none", "convert.1": "exchange.unpack",
         "multiply.1": "optimizer"}


def _trace(steps: int = 2) -> dict:
    """``steps`` steps of 1000 ns of ops: forward 100, backward 400 (a loop
    of 300 whose body runs 150 + 100), pack 50, buckets 60 + 40, unpack 30,
    optimizer 90 + 80, mixed 70, none 80; an op the map does not hold 0."""
    host, ops = [["bench.window", 0, 1000 * steps + 10]], []
    for k in range(steps):
        t = 1000 * k
        host.append(["bench.step", t, 5])
        ops += [["dot.1 dot f32[8]", t, 100],
                ["while.1 while tuple", t + 100, 400],
                ["dot.9 dot f32[8]", t + 150, 150],
                ["exp.9 exponential f32[8]", t + 350, 100],
                ["concatenate.1 concatenate f32[8]", t + 500, 50],
                ["all-reduce.1 all-reduce f32[8]", t + 550, 60],
                ["all-reduce.2 all-reduce f32[8]", t + 610, 40],
                ["slice.1 slice f32[8]", t + 650, 30],
                ["fusion.1 fusion f32[8]", t + 680, 70],
                ["fusion.2 fusion f32[8]", t + 750, 90],
                ["copy.1 copy f32[8]", t + 840, 80],
                ["multiply.2 multiply f32[8]", t + 920, 80]]
    return {"host": host, "devices": {"/device:TPU:0": {"ops": ops, "async": []}}}


def _run(tr):
    return SimpleNamespace(trace=tr)


@pytest.fixture(autouse=True)
def fresh():
    layers._last[:] = None, None
    yield
    layers._last[:] = None, None


def test_layer_map_of_the_miniature_step():
    got = hlo.layers(HLO)
    assert {k: got[k] for k in LAYER} == LAYER
    assert set(got.values()) <= set(hlo.LAYERS)


def test_layer_times_add_up_to_the_busy_time(monkeypatch):
    monkeypatch.setattr(layers, "module_text", lambda: HLO)
    tr = _trace()
    t = layers.times(_run(tr))
    ns = 1e-6
    assert t["forward"] == pytest.approx(100 * ns)
    assert t["backward"] == pytest.approx(400 * ns)
    assert t["exchange.bucket"] == pytest.approx(100 * ns)
    assert t["mixed"] == pytest.approx(70 * ns) and t["none"] == pytest.approx(80 * ns)
    values = {m: spec.reader(REPO, m)(_run(tr)) for m in READERS}
    assert values["exchange.pack_unpack_ms"] == pytest.approx(80 * ns)
    assert values["optimizer.update_ms"] == pytest.approx(170 * ns)
    assert values["step.unattributed_share"] == pytest.approx(15.0)
    busy = devtrace.per_device(tr)["/device:TPU:0"]["busy"] * ns / devtrace.steps(tr)
    total = (values["model.forward_ms"] + values["model.backward_ms"]
             + values["exchange.pack_unpack_ms"] + values["optimizer.update_ms"]
             + t["exchange.bucket"]
             + values["step.unattributed_share"] / 100 * sum(t.values()))
    assert total == pytest.approx(busy)


def test_an_op_outside_the_map_is_unattributed(monkeypatch):
    monkeypatch.setattr(layers, "module_text", lambda: HLO)
    tr = _trace(1)
    tr["devices"]["/device:TPU:0"]["ops"].append(["copy-start.5 copy-start f32[8]", 1000, 5])
    assert layers.times(_run(tr))["none"] == pytest.approx(85e-6)


@pytest.mark.parametrize("case", ["no trace", "no device", "older program",
                                  "no executable", "no scope"])
def test_readers_return_none(monkeypatch, case):
    tr = _trace()
    monkeypatch.setattr(layers, "module_text", lambda: HLO)
    if case == "no trace":
        tr = None
    elif case == "no device":
        tr["devices"] = {}
    elif case == "older program":
        monkeypatch.delattr(hlo, "layers")
    elif case == "no scope":          # compiled from a program without them
        monkeypatch.setattr(layers, "module_text",
                            lambda: re.sub(r"(exchange|optimizer|model)", "x", HLO))
    else:
        monkeypatch.setattr(layers, "module_text", lambda: None)
    assert all(spec.reader(REPO, m)(_run(tr)) is None for m in READERS)


def test_module_text_finds_the_one_live_step(monkeypatch):
    """By module name, among the process's live executables; none where
    there are two.  (A name of its own, so that steps other tests left
    alive do not count.)"""
    import jax
    import jax.numpy as jnp

    def probe_step(x):
        return x * 2

    monkeypatch.setattr(layers, "MODULE", "jit_probe_step")
    assert layers.module_text() is None
    compiled = jax.jit(probe_step).lower(jnp.ones(3)).compile()
    text = layers.module_text()
    assert text.startswith("HloModule jit_probe_step") and "op_name" in text
    other = jax.jit(probe_step).lower(jnp.ones(4)).compile()
    assert layers.module_text() is None
    del compiled, other


def test_layer_times_add_up_on_the_recorded_chip_slice():
    """The end of a step on the chip, with the compiled step's own layer
    map: every op's own time goes to one layer, and the layers add up to
    the busy time."""
    tr = json.loads((DATA / "whisper-1chip-layers-slice.json").read_text())
    t = layers.per_step_ms(tr, tr["layers"])
    (per,) = devtrace.per_device(tr).values()
    assert devtrace.steps(tr) == 1
    assert sum(t.values()) == pytest.approx(per["busy"] * 1e-6, rel=1e-3)
    assert t["optimizer"] > t["exchange.pack"] > 0 and t["mixed"] > 0
    assert t["exchange.bucket"] == 0                     # one chip
