"""Tiny cells for CPU tests: a copy of the benchmark in a temporary root
with small configurations of both families added as new files."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
CPU_PEAKS = {"cpu": {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}}

_COMMON = {"optimizer": {"name": "adamw", "lr": 1e-3, "schedule": "constant",
                         "b1": 0.9, "b2": 0.98, "eps": 1e-6,
                         "weight_decay": 0.1, "clip_norm": 1.0},
           "reduced": [], "departures": [], "deployment": "a CPU test"}

CONFIGS = {
    "tiny-encdec": {
        "name": "tiny-encdec", "source": "test", "family": "encdec",
        "registry": "whisper-base",
        "overrides": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                      "num_kv_heads": 4, "head_dim": 16, "d_ff": 128,
                      "vocab_size": 500, "encoder_layers": 2,
                      "encoder_seq": 32, "logit_chunk": 16, "attn_chunk": 16},
        "d_model": 64, "encoder_layers": 2, "decoder_layers": 2,
        "encoder_attention_heads": 4, "decoder_attention_heads": 4,
        "encoder_ffn_dim": 128, "decoder_ffn_dim": 128, "vocab_size": 500,
        "max_source_positions": 32, "max_target_positions": 16,
        "assumed": {"norm_eps": 1e-5, "rope_theta": 10000.0,
                    "dtype": "bfloat16", "vocab_rows": 512}, **_COMMON},
    "tiny-decoder": {
        "name": "tiny-decoder", "source": "test", "family": "decoder",
        "registry": "stablelm-3b",
        "overrides": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                      "num_kv_heads": 4, "head_dim": 16, "d_ff": 128,
                      "vocab_size": 500, "logit_chunk": 16, "attn_chunk": 16},
        "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 128, "vocab_size": 500,
        "max_position_embeddings": 64, "tie_word_embeddings": False,
        "assumed": {"norm_eps": 1e-5, "rope_theta": 10000.0,
                    "dtype": "bfloat16", "vocab_rows": 512}, **_COMMON},
}

COMM = {"explicit": {"mode": "explicit", "compression": "none",
                     "scheduler": "fifo", "fusion_buffer_mb": 64.0},
        "auto": {"mode": "auto", "compression": "none", "scheduler": "fifo",
                 "fusion_buffer_mb": 64.0}}

# limits of the tiny cells on the CPU, set as a cell's are: above what the
# bf16 program reads against the reference (at most 3.5e-4, 2.8e-3, 1.6e-3,
# 1.5e-3 on a test seed), below what the float8 control reads (at least
# 4.5e-3, 3.7e-2, 3.2e-2, 7.0e-3)
LIMITS = {"loss": 0.002, "grad_norm": 0.01, "grad_leaf": 0.01, "update_leaf": 0.005}


def make_root(tmp: Path, cells: list[tuple[str, str, str, int]]) -> Path:
    """A benchmark root under ``tmp``: the repo's BENCHMARK.json and bench/
    copied, ``src`` linked, and each (cell, config, comm, chips) added as a
    configuration, traffic and limits file and a BENCHMARK.json entry."""
    root = tmp / "root"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for cell, config, comm, chips in cells:
        c = CONFIGS[config]
        (root / "bench" / "configs" / f"{config}.json").write_text(json.dumps(c))
        if config not in {x["name"] for x in bench["configs"]}:
            bench["configs"].append({"name": config, "source": "test",
                                     "file": f"bench/configs/{config}.json",
                                     "reduced": [], "why": "CPU test"})
        seq = c.get("max_target_positions") or c.get("max_position_embeddings") // 2
        traffic = {"batch_per_chip": 2, "seq_len": seq, "zipf_a": 1.2,
                   "pool": 3, "comm": COMM[comm]}
        (root / "bench" / "traffic" / f"{cell}.json").write_text(json.dumps(traffic))
        (root / "bench" / "limits" / f"{cell}.json").write_text(
            json.dumps({"limits": LIMITS}))
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": cell, "chips": chips,
                                   "why": "CPU test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root
