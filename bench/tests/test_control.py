"""The control: the reference computed in float8, put in the program's
place, fails a limit, while the program passes them (tiny size, CPU)."""
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "src"))


@pytest.mark.parametrize("config,comm", [("tiny-encdec", "explicit"),
                                         ("tiny-decoder", "auto")])
def test_float8_control_is_not_correct(tmp_path, config, comm):
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    from bench import check, harness, spec
    from bench.tests import tiny
    root = tiny.make_root(tmp_path, [("c", config, comm, 1)])
    cell = harness.Cell(spec.load(root, "c"), jax.devices()[:1])
    cell.start(2**31 + 3)
    prog = cell.checked_steps()
    cell.stop_feed()
    cell.free()
    ref = cell.reference()
    limits = {"limits": tiny.LIMITS}
    assert check.passed(check.compare(prog, ref, limits))
    control = check.compare(cell.reference(low=True), ref, limits)
    assert not check.passed(control), control
