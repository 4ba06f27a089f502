"""A cell and a per-layer metric added only as new files are found and run
by the harness, with no edit of a file that is there."""
import hashlib
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "src"))


def _digests(root: Path) -> dict:
    return {p.relative_to(root).as_posix(): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_cell_and_metric_as_files(tmp_path):
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    from bench import harness, spec
    from bench.tests import tiny
    root = tiny.make_root(tmp_path, [])
    before = _digests(root)
    # the new files: a configuration, a traffic mix, limits, a metric reader
    root = tiny.make_root(tmp_path / "new", [("tiny.new", "tiny-decoder", "auto", 1)])
    (root / "bench" / "metrics" / "loop.steps_seen.py").write_text(
        "def read(run):\n    return float(run.steps)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "loop.steps_seen", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "entry loop", "moves": "tokens_per_s",
                               "workloads": ["tiny.new"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = _digests(root)
    assert all(after[k] == v for k, v in before.items())   # nothing edited
    assert set(after) - set(before)                       # only added

    cs = spec.load(root, "tiny.new")
    assert [m["name"] for m in cs.per_layer][-1] == "loop.steps_seen"
    r = harness.run(cs, 12345, 0.5, True, t0=time.perf_counter(),
                    require_tpu=False, peaks=tiny.CPU_PEAKS)
    assert r["correct"], r["checks"]
    assert r["metrics"]["loop.steps_seen"]["value"] == r["attempted"] >= 1
    assert "input.wait_ms" in r["metrics"] and "tokens_per_s" not in r["metrics"]
