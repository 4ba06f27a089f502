"""Operation counts from shapes, and the table of peaks."""
import json
from pathlib import Path

import pytest

from bench import flops

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def test_whisper_base_forward_at_32_sequences():
    c = json.loads((CONFIGS / "whisper-base.json").read_text())
    assert flops.forward(c, 32, 448) == pytest.approx(5.48e12, rel=1e-3)
    assert flops.step(c, 32, 448) == 3 * flops.forward(c, 32, 448)


def test_stablelm_forward_at_6_layers_and_4096_tokens():
    c = json.loads((CONFIGS / "stablelm-3b-4e1t.json").read_text())
    assert c["num_hidden_layers"] == 6
    assert flops.forward(c, 1, 4096) == pytest.approx(5.47e12, rel=1e-3)


def test_flash_attention_counts_the_causal_half():
    ops, nbytes = flops.flash_attention(32, 4096, 80)
    assert ops == 2 * 32 * 4096 ** 2 * 80
    assert nbytes == 4 * 32 * 4096 * 80 * 2


def test_peaks_of_the_v5e_and_an_unknown_kind():
    p = flops.peak("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peak("TPU v9 imaginary")
