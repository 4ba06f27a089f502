"""Readings from which a cell's limits are set; not part of a benchmark run.

For each seed, in one process with one compiled step: the program's three
checked steps through the window's own call and feed, the reference, and
each number of bench/check.py for

- ``program``: the program against the reference (sound runs: the lower
  reading);
- ``control``: the reference computed in float8, put in the program's
  place (the upper reading);
- ``half``: the reference over half of each batch (half of the rows, or of
  each row's tokens where a batch is one row);
- ``no_exchange`` (several chips): the gradient of the first chip's rows
  alone, the loss over all;
- ``sum_exchange`` (several chips): the gradient summed over the chips
  instead of averaged.

A state left unchanged reads 1 on ``update_leaf`` and needs no run.

  python3 bench/calibrate.py --workload <name> --seeds 11,12,13 [--faults 1]

Prints one JSON line per seed, with every reading that the numbers are
made from, and appends it to chiprun_out/calibrate/<workload>.jsonl.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import check, harness, spec  # noqa: E402


def _plain(r: dict) -> dict:
    keys = ("losses", "grad_norms", "grad_leaf", "update_leaf", "raw_grad_leaf")
    return {k: [float(x) for x in r[k]] for k in keys if k in r}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", type=int, default=1,
                    help="0: the program alone, without control and faults")
    args = ap.parse_args(argv)
    cs = spec.load(ROOT, args.workload)
    devices, err = harness.find_devices(cs.chips)
    if err:
        print(f"calibrate: {err}", file=sys.stderr)
        return 2
    from repro.launch import train
    train.enable_compile_cache()
    cell = harness.Cell(cs, devices)
    out = ROOT / "chiprun_out" / "calibrate"
    out.mkdir(parents=True, exist_ok=True)
    B = cell.rows
    faults = {}
    if args.faults:
        faults["half"] = (dict(loss_rows=B // 2, grad_rows=B // 2) if B >= 2
                          else dict(half_tokens=True))
        if cs.chips > 1:
            faults["no_exchange"] = dict(grad_rows=B // cs.chips)
            faults["sum_exchange"] = dict(grad_scale=float(cs.chips))
    with open(out / f"{args.workload}.jsonl", "a") as f:
        f.write(json.dumps({"leaf_names": cell.leaf_names}) + "\n")
        for seed in (int(s) for s in args.seeds.split(",")):
            t = time.perf_counter()
            cell.start(seed)
            prog = cell.checked_steps()
            cell.stop_feed()
            cell.free()
            ref = cell.reference()
            runs = {"program": prog}
            if args.faults:
                runs["control"] = cell.reference(low=True)
            for name, kw in faults.items():
                runs[name] = cell.reference(**kw)
            row = {"seed": seed, **{k: check.numbers(v, ref) for k, v in runs.items()}}
            row["readings"] = {k: _plain(v) for k, v in {"reference": ref, **runs}.items()}
            row["seconds"] = time.perf_counter() - t
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
