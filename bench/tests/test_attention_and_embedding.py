"""The attention count (``bench/flops/attention.py``) against hand-worked
numbers, the readers ``flash_attn_roofline`` and ``embed.grad_ms`` on a
hand-made trace, and the program's ``embed.grad`` scope in the layer map of
the tiny cells' compiled steps (CPU)."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "src"))

from bench import layers, spec  # noqa: E402
from bench.flops import attention  # noqa: E402
from repro.utils import hlo  # noqa: E402

CONFIGS = REPO / "bench" / "configs"
PEAK = {"bf16_flops": 1e15, "hbm_bytes_per_s": 1e12}


def _config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_stablelm_attention_at_one_4096_token_sequence():
    # 6 layers x 2 x 4096^2 x 2560: q k^T and p v (4 S^2 D), causal half
    c = _config("stablelm-3b-4e1t")
    assert attention.forward(c, 1, 4096) == 6 * 2 * 4096 ** 2 * 2560 == 515_396_075_520
    assert attention.step(c, 1, 4096) == 3 * 515_396_075_520


def test_whisper_base_attention_at_32_sequences():
    # per row: 6 encoder layers x 4 x 1500^2 x 512 = 27,648,000,000;
    # 6 decoder layers x (2 x 448^2 x 512 + 4 x 448 x 1500 x 512)
    #   = 6 x (205,520,896 + 1,376,256,000) = 9,490,661,376
    c = _config("whisper-base")
    assert attention.forward(c, 1, 448) == 27_648_000_000 + 9_490_661_376
    assert attention.forward(c, 32, 448) == 32 * 37_138_661_376
    assert attention.step(c, 32, 448) == 3 * attention.forward(c, 32, 448)


def test_an_unknown_family_is_an_error():
    with pytest.raises(ValueError):
        attention.forward({"family": "ssm"}, 1, 8)


def _trace(ops, steps=2):
    host = [["bench.window", 0, 1000 * steps]]
    host += [["bench.step", 1000 * k, 5] for k in range(steps)]
    return {"host": host, "devices": {"/device:TPU:0": {"ops": ops, "async": []}}}


def _run(tr, config="stablelm-3b-4e1t", rows=1, seq_len=4096):
    return SimpleNamespace(trace=tr, config=_config(config), chips=1, peak=PEAK,
                           traffic={"batch_per_chip": rows, "seq_len": seq_len})


def test_roofline_counts_every_splash_kernel_in_the_window():
    # two steps, each with a forward (300 ns) and a fused backward (700 ns);
    # a kernel outside the window and an op of another name do not count
    ops = []
    for k in range(2):
        t = 1000 * k
        ops += [["splash_mha_fwd_residuals.3 custom-call:tpu_custom_call tuple", t, 300],
                ["splash_mha_dkv_no_residuals.4 custom-call:tpu_custom_call tuple",
                 t + 300, 700],
                ["fusion.9 fusion bf16[8]", t + 100, 50]]
    ops.append(["splash_mha_fwd_residuals.3 custom-call:tpu_custom_call tuple", 5000, 9])
    got = spec.reader(REPO, "flash_attn_roofline")(_run(_trace(ops)))
    want = 100 * 2 * attention.step(_config("stablelm-3b-4e1t"), 1, 4096) / (
        2000e-9 * PEAK["bf16_flops"])
    assert got == pytest.approx(want)


@pytest.mark.parametrize("case", ["no trace", "no device", "no splash op"])
def test_roofline_is_none_without_a_kernel(case):
    tr = _trace([["fusion.9 fusion bf16[8]", 0, 50]])
    if case == "no trace":
        tr = None
    elif case == "no device":
        tr["devices"] = {}
    assert spec.reader(REPO, "flash_attn_roofline")(_run(tr)) is None


HLO = """HloModule jit_train_step, is_scheduled=true

ENTRY %main (param.1: f32[8]) -> bf16[8] {
  %param.1 = f32[8]{0} parameter(0), metadata={op_name="params['w']"}
  %dot.1 = f32[8]{0} dot(%param.1, %param.1), metadata={op_name="jit(train_step)/jvp(model)/dot_general"}
  %dot.2 = f32[8]{0} dot(%dot.1, %dot.1), metadata={op_name="jit(train_step)/transpose(jvp(model))/dot_general"}
  %scatter.3 = f32[8]{0} scatter(%dot.2, %param.1, %dot.2), metadata={op_name="jit(train_step)/transpose(jvp(model))/embed.grad/scatter-add"}
  ROOT %convert.4 = bf16[8]{0} convert(%scatter.3), metadata={op_name="jit(train_step)/transpose(jvp(model))/embed.grad/convert_element_type"}
}
"""


@pytest.fixture
def fresh():
    layers._last[:] = None, None
    yield
    layers._last[:] = None, None


def test_embed_grad_ms_reads_its_scope(monkeypatch, fresh):
    monkeypatch.setattr(layers, "module_text", lambda: HLO)
    ops = []
    for k in range(2):
        t = 1000 * k
        ops += [["dot.1 dot f32[8]", t, 100], ["dot.2 dot f32[8]", t + 100, 200],
                ["scatter.3 scatter f32[8]", t + 300, 30],
                ["convert.4 convert bf16[8]", t + 330, 20]]
    got = spec.reader(REPO, "embed.grad_ms")(SimpleNamespace(trace=_trace(ops)))
    assert got == pytest.approx(50e-6)


def test_embed_grad_ms_is_none_for_a_program_without_the_scope(monkeypatch, fresh):
    monkeypatch.setattr(layers, "module_text", lambda: HLO.replace("embed.grad/", ""))
    monkeypatch.setattr(hlo, "LAYERS", tuple(x for x in hlo.LAYERS if x != "embed.grad"))
    tr = _trace([["dot.1 dot f32[8]", 0, 100]])
    assert spec.reader(REPO, "embed.grad_ms")(SimpleNamespace(trace=tr)) is None


@pytest.mark.parametrize("config,comm", [("tiny-decoder", "auto"),
                                         ("tiny-encdec", "explicit")])
def test_the_compiled_step_maps_instructions_to_embed_grad(config, comm):
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    from bench import harness
    from bench.tests import tiny
    traffic = {"batch_per_chip": 2, "seq_len": 16, "zipf_a": 1.2, "pool": 3,
               "comm": tiny.COMM[comm]}
    cs = spec.CellSpec("c", 1, tiny.CONFIGS[config], traffic, {}, [], [], REPO)
    cell = harness.Cell(cs, jax.devices()[:1])
    cell.start(2**31 + 5)
    cell.stop_feed()
    got = set(hlo.layers(cell.compiled.as_text()).values())
    cell.free()
    assert {"forward", "backward", hlo.EMBED_GRAD, "optimizer"} <= got
