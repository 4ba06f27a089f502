"""Benchmark harness entry point: ``PYTHONPATH=src python -m benchmarks.run``.

Sections:
  1. Paper figures/tables (fig1-fig8 + transmission table) with validation
     checks against the paper's own numbers.
  2. Kernel micro-benchmarks (Pallas interpret-mode vs jnp ref).
  3. TPU what-if for the assigned architectures (beyond-paper).

Prints ``name,us_per_call,derived`` CSV per benchmark plus a validation
summary; exits non-zero if a paper-claim check fails.
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))


def main() -> int:
    failures = 0

    # -- 1. paper figures (experiment engine -> artifact -> report) ---------
    import time

    from repro.experiments import artifacts, compare, grids, run_suite

    print("=" * 72)
    print("SECTION 1: paper figure reproductions (experiment engine)")
    print("=" * 72)
    art_path = Path(__file__).resolve().parent.parent / "artifacts" / \
        "experiments" / "paper.json"
    t0 = time.perf_counter()
    records = run_suite(grids.resolve("paper"))
    suite_us = (time.perf_counter() - t0) * 1e6
    art = artifacts.write(art_path, records, meta={"grid": "paper"})
    print(f"suite artifact: {art_path} ({suite_us:.0f} us total)")
    for ex in art["experiments"]:
        val = ex["validations"]
        ok = all(val.values())
        failures += 0 if ok else 1
        print(f"\n{ex['name']},{len(ex['cells'])}cells,"
              f"{'PASS' if ok else 'FAIL'}")
        for k, v in val.items():
            print(f"  check {k}: {'ok' if v else 'FAIL'}")
        for c in ex["cells"][:6]:
            print(f"  {c['model']},srv={c['n_servers']},"
                  f"bw={c['bandwidth_gbps']:g},{c['transport']},"
                  f"r={c['compression_ratio']:g},{c['topology']}: "
                  f"f_sim={c['scaling_factor']:.4f} "
                  f"util={c['network_utilization']:.3f}")
        if len(ex["cells"]) > 6:
            print(f"  ... ({len(ex['cells'])} cells total)")

    golden = Path(__file__).resolve().parent.parent / "artifacts" / \
        "golden" / "paper_suite.json"
    if golden.exists():
        report = compare(artifacts.read(golden), art)
        failures += 0 if report.ok else 1
        print(f"\ngolden-artifact gate: {report.summary()}")

    # the scheduling axis the event engine opened (tentpole): fifo vs
    # priority vs chunked over the paper bandwidths, gated by its own golden
    sched_records = run_suite(grids.resolve("scheduler"))
    sched_art = artifacts.make_artifact(sched_records)
    for ex in sched_art["experiments"]:
        val = ex["validations"]
        ok = all(val.values())
        failures += 0 if ok else 1
        print(f"\n{ex['name']},{len(ex['cells'])}cells,"
              f"{'PASS' if ok else 'FAIL'}")
        for k, v in val.items():
            print(f"  check {k}: {'ok' if v else 'FAIL'}")
    sched_golden = golden.parent / "scheduler_suite.json"
    if sched_golden.exists():
        report = compare(artifacts.read(sched_golden), sched_art)
        failures += 0 if report.ok else 1
        print(f"scheduler-golden gate: {report.summary()}")

    from benchmarks.figures import scheduler_contention
    rows, cval = scheduler_contention()
    cok = all(bool(v) for k, v in cval.items() if k != "us")
    failures += 0 if cok else 1
    print(f"\nscheduler_contention,{cval.get('us', 0):.0f},"
          f"{'PASS' if cok else 'FAIL'}")
    for r in rows:
        print(f"  {r}")

    # non-sweep figures keep their direct analyses
    from benchmarks.figures import fig2_computation_time, table_transmission
    for name, fn in (("fig2_computation_time", fig2_computation_time),
                     ("table_transmission", table_transmission)):
        rows, val = fn()
        us = val.pop("us", 0.0)
        ok = all(bool(v) for v in val.values())
        failures += 0 if ok else 1
        print(f"\n{name},{us:.0f},{'PASS' if ok else 'FAIL'}")
        for k, v in val.items():
            print(f"  check {k}: {'ok' if v else 'FAIL'}")
        for r in rows[:6]:
            print(f"  {r}")
        if len(rows) > 6:
            print(f"  ... ({len(rows)} rows total)")
    from benchmarks.figures import bytescheduler_bound
    bs, bs_ok = bytescheduler_bound()
    failures += 0 if bs_ok else 1
    print(f"\nbytescheduler_whatif,0,{'PASS' if bs_ok else 'FAIL'}")
    print(f"  {bs}")

    # -- 2. kernels -----------------------------------------------------------
    print("\n" + "=" * 72)
    print("SECTION 2: kernel micro-benchmarks (interpret mode on CPU)")
    print("=" * 72)
    from benchmarks.kernel_bench import run as kernel_run
    print("name,us_per_call,derived")
    for r in kernel_run():
        print(f"{r['name']},{r['us_per_call']:.0f},{r['derived']}")

    # -- 3. TPU what-if -------------------------------------------------------
    print("\n" + "=" * 72)
    print("SECTION 3: TPU what-if for assigned architectures (beyond-paper)")
    print("=" * 72)
    from repro.configs import INPUT_SHAPES, get_config
    from repro.core.whatif import tpu_whatif
    shape = INPUT_SHAPES["train_4k"]
    print("name,us_per_call,derived")
    for arch in ("stablelm-3b", "command-r-35b", "deepseek-coder-33b",
                 "rwkv6-1.6b", "jamba-v0.1-52b", "moonshot-v1-16b-a3b"):
        for n_pods in (1, 2):
            r = tpu_whatif(get_config(arch), shape, n_pods=n_pods)
            print(f"tpu_whatif[{arch},pods={n_pods}],0,"
                  f"f_sim={r.scaling_factor:.3f};overhead_ms="
                  f"{r.t_overhead*1e3:.2f}")

    print(f"\n{'ALL BENCHMARKS PASS' if failures == 0 else f'{failures} FAILURES'}")
    return failures


if __name__ == "__main__":
    raise SystemExit(main())
