"""Chip smoke test: whisper-base trained through ``repro.launch.train`` on TPU.

Whisper-base at its published widths (arXiv:2212.04356; d_model 512, 6+6
layers, 8 heads, FFN 2048, 1500 encoder frames), 448 decoder tokens, AdamW
at base's published peak learning rate (1e-3), random weights from seed 0.
Three phases of ``STEPS`` steps each, all in this one process:

  (a) --comm-mode auto                          XLA SPMD gradient averaging
  (b) --comm-mode explicit --compression none   bucketed exchange
  (c) --comm-mode explicit --compression int8   + Pallas codec kernels

Default: one chip, global batch 32.  ``--four-chips``: the same three phases
on a 4-chip ``data`` mesh at global batch 128 (32 per chip), and nothing else.

Checks: every loss is finite; (b) matches (a) at every step within
``REL_TOL_EXACT`` and (c) within ``REL_TOL_INT8`` (relative); each phase
compiles ``train_step`` once; the compiled (c) step holds ``tpu_custom_call``
(the codec kernels, compiled).  On four chips, the explicit steps' payload
collectives are exactly the bucket plan's exchange, with none inserted by
XLA's SPMD partitioner.

Prints one JSON line per phase (step time, tokens/s, compile time and count,
peak device memory, custom calls, collectives, losses), then, when every
check passed, the last line
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": n}}``.
Exits non-zero without that line when the default JAX backend is not a TPU,
when the device count is not the one asked for, or when a check fails.

  python chip_smoke.py                # one chip
  python chip_smoke.py --four-chips   # one host with four chips
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

STEPS = 5
SEQ_LEN = 448              # whisper's decoder context
BATCH_PER_CHIP = 32
PEAK_LR = 1e-3             # whisper-base's published peak learning rate
# (b) runs the same arithmetic as (a) but in another program, so bf16
# rounding may differ; (c) adds int8 quantization of every gradient.
REL_TOL_EXACT = 1e-3
REL_TOL_INT8 = 1e-2
PHASES = (("a", "auto", "none"), ("b", "explicit", "none"),
          ("c", "explicit", "int8"))


def max_rel_diff(xs, ys):
    return max(abs(x - y) / abs(y) for x, y in zip(xs, ys))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the phases on a 4-chip data mesh")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's default backend is "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    if len(devices) != n_chips:
        print(f"chip_smoke: needs {n_chips} chip(s), JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import CommConfig
    from repro.launch import train
    from repro.parallel.grad_sync import exchange_collectives

    train.enable_compile_cache()
    results, failures = {}, []
    for name, mode, compression in PHASES:
        r = train.main([
            "--arch", "whisper-base", "--steps", str(STEPS),
            "--batch", str(BATCH_PER_CHIP * n_chips),
            "--seq-len", str(SEQ_LEN), "--lr", str(PEAK_LR),
            "--warmup", "1", "--log-every", "1",
            "--comm-mode", mode, "--compression", compression])
        results[name] = r
        print(json.dumps({
            "phase": name, "comm": f"{mode}/{compression}",
            "chips": r["devices"], "global_batch": r["global_batch"],
            "seq_len": r["seq_len"],
            "median_step_ms": r["median_step_s"] * 1e3,
            "tokens_per_s": r["tokens_per_s"], "compile_s": r["compile_s"],
            "compiles": r["compiles"],
            "peak_bytes_in_use": r["peak_bytes_in_use"],
            "temp_bytes": r["temp_bytes"],
            "argument_bytes": r["argument_bytes"],
            "tpu_custom_calls": r["tpu_custom_calls"],
            "n_buckets": r["n_buckets"], "collectives": r["collectives"],
            "losses": r["losses"]}), flush=True)

        if not all(math.isfinite(x) for x in r["losses"]):
            failures.append(f"({name}) non-finite loss: {r['losses']}")
        if r["compiles"] != 1:
            failures.append(f"({name}) train_step compiled {r['compiles']} "
                            f"times")
        if mode == "explicit" and n_chips > 1:
            coll = r["collectives"]
            want = exchange_collectives(
                CommConfig(compression=compression), r["n_buckets"])
            got = sum(coll["exchange"].values())
            if coll["spmd"] or got != want:
                failures.append(
                    f"({name}) payload collectives {coll} are not the bucket "
                    f"plan's {want} exchanges alone")

    if results["c"]["tpu_custom_calls"] == 0:
        failures.append("(c) compiled step holds no tpu_custom_call")
    for name, tol in (("b", REL_TOL_EXACT), ("c", REL_TOL_INT8)):
        d = max_rel_diff(results[name]["losses"], results["a"]["losses"])
        print(json.dumps({"compare": f"{name} vs a", "max_rel_diff": d,
                          "tolerance": tol}), flush=True)
        if not d <= tol:
            failures.append(f"({name}) losses differ from (a) by {d:.3g} "
                            f"relative, above {tol}")

    if failures:
        for f in failures:
            print(f"FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
