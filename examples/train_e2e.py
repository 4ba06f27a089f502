"""End-to-end training driver: train a ~100M-param dense model with the
paper's communication phase enabled (bucketed grad-sync + optional
compression), checkpointing, and scaling-factor instrumentation.

Defaults are sized for this CPU container (~100M params, short run); pass
``--steps 300`` for the full few-hundred-step run on real hardware.

Run:  PYTHONPATH=src python examples/train_e2e.py --steps 30
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.configs import register  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402


@register("repro-100m")
def repro_100m() -> ModelConfig:
    """~100M-param llama-style dense config for the e2e example."""
    return ModelConfig(
        name="repro-100m", family="dense", num_layers=8, d_model=768,
        num_heads=12, num_kv_heads=4, d_ff=2048, vocab_size=32000,
        attn_chunk=256, logit_chunk=256, dtype="float32", remat=False,
        sharding="dp_tp", source="examples/train_e2e.py")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--compression", default="none")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_e2e_ckpt")
    args = ap.parse_args()

    from repro.launch import train as train_mod

    argv = ["--arch", "repro-100m", "--shape", "train_4k",
            "--steps", str(args.steps), "--batch", str(args.batch),
            "--seq-len", str(args.seq_len),
            "--comm-mode", "explicit", "--compression", args.compression,
            "--ckpt-dir", args.ckpt_dir, "--ckpt-every", "10",
            "--log-every", "1"]
    result = train_mod.main(argv)
    assert result["loss_decreased"], "loss must decrease over the run"
    print(f"[e2e] OK — loss {result['first_loss']:.3f} -> "
          f"{result['last_loss']:.3f}")


if __name__ == "__main__":
    main()
