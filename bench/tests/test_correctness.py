"""The program against the reference at a tiny size on the CPU, on 1 and
on 4 virtual devices, and each planted fault of the timed path coming out
not correct (bench/tests/drive.py)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

CASES = [
    # cell, config, comm, chips, fault, correct
    ("enc1", "tiny-encdec", "explicit", 1, "none", True),
    ("enc4", "tiny-encdec", "explicit", 4, "none", True),
    ("dec1", "tiny-decoder", "auto", 1, "none", True),
    ("enc4", "tiny-encdec", "explicit", 4, "sum_exchange", False),
    ("enc4", "tiny-encdec", "explicit", 4, "no_exchange", False),
    ("enc4", "tiny-encdec", "explicit", 4, "half", False),
    ("enc1", "tiny-encdec", "explicit", 1, "unchanged", False),
    ("dec1", "tiny-decoder", "auto", 1, "unchanged", False),
    ("dec1", "tiny-decoder", "auto", 1, "half", False),
]


def drive(tmp_path, cell, config, comm, chips, fault) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={chips}"}
    p = subprocess.run([sys.executable, "-m", "bench.tests.drive", cell, config,
                        comm, str(chips), fault, str(tmp_path)],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,config,comm,chips,fault,correct", CASES,
                         ids=[f"{c[0]}-{c[4]}" for c in CASES])
def test_fault_decides_correct(tmp_path, cell, config, comm, chips, fault, correct):
    r = drive(tmp_path, cell, config, comm, chips, fault)
    assert r["device"]["count"] == chips
    assert r["correct"] is correct, r["checks"]
    if fault == "sum_exchange":        # the scale shows in the norm before clipping
        c = r["checks"]["grad_norm"]
        assert c["value"] > c["limit"]
    if fault == "unchanged":
        assert r["checks"]["update_leaf"]["value"] == pytest.approx(1.0)
