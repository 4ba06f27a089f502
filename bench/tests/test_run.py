"""bench/run.py refuses, with no result, where it cannot measure."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "whisper-base.1chip.bucketed", "--seed", str(2**31 + 5),
        "--seconds", "1", "--trace", "0"]


def _run(root: Path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    p = _run(REPO)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "needs a TPU" in p.stderr


def test_with_only_the_benchmark_files_it_exits_nonzero(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
