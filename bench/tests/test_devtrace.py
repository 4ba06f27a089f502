"""The reduction from a trace to numbers, on a small recorded chip trace and
on a hand-made one with collectives."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import devtrace

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded():
    return json.loads((DATA / "whisper-1chip-slice.json").read_text())


def _brute_busy(ops, lo, hi, res=10.0):
    """Busy ns by marking 10 ns bins: an independent count of the union."""
    n = int((hi - lo) / res) + 1
    bins = np.zeros(n, bool)
    for _, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            bins[int((a - lo) / res):int((b - lo) / res)] = True
    return bins.sum() * res


def test_busy_and_idle_on_the_recorded_trace(recorded):
    lo, hi = devtrace.window(recorded)
    (dev, d), = recorded["devices"].items()
    per = devtrace.per_device(recorded)[dev]
    assert per["window"] == hi - lo
    assert per["busy"] == pytest.approx(_brute_busy(d["ops"], lo, hi),
                                        abs=20.0 * len(d["ops"]))
    assert 0 < per["busy"] < per["window"]
    assert per["collective"] == 0 and per["exposed"] == 0   # one chip
    assert devtrace.steps(recorded) == 1


def test_own_times_add_up_to_the_outermost_operations(recorded):
    lo, hi = devtrace.window(recorded)
    (d,) = recorded["devices"].values()
    ops = sorted((o for o in d["ops"] if lo <= o[1] < hi), key=lambda o: (o[1], -o[2]))
    outer, end = 0.0, -1.0
    for _, s, dur in ops:
        if s >= end:
            outer, end = outer + dur, s + dur
    own = devtrace.self_times(d["ops"], lo, hi)
    assert sum(own.values()) == pytest.approx(outer)
    assert all(v >= -1.0 for v in own.values())


def test_breakdown_names_gaps_by_host_span(recorded):
    b = devtrace.breakdown(recorded)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(g[0] in ("bench.input_wait", "bench.step", "bench.block", "bench.none")
               for g in b["idle_gaps"])
    secs = [g[1] for g in b["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)


def test_collective_and_exposed_time_by_hand():
    tr = {"host": [["bench.window", 0, 100], ["bench.step", 1, 2]],
          "devices": {"/device:TPU:0": {
              "ops": [["fusion.1 fusion f32[8]", 0, 10],
                      ["all-reduce.2 all-reduce f32[8]", 8, 6],
                      ["fusion.3 fusion f32[8]", 15, 2]],
              "async": [["all-gather-start.4 all-gather-start tuple", 12, 8]]}}}
    per = devtrace.per_device(tr)["/device:TPU:0"]
    assert per["busy"] == 16                 # [0, 14) and [15, 17)
    assert per["collective"] == 12           # [8, 20)
    assert per["exposed"] == 8               # [10, 15) and [17, 20)
    assert devtrace.kernel_time(tr, r"^fusion") == (2, 12)


def test_label_of_hlo_instructions():
    flash = ('%closed_call.123 = bf16[32,4096,80]{2,1,0:T(8,128)(2,1)} custom-call('
             'bf16[32,4096,80]{2,1,0} %custom-call.164), custom_call_target='
             '"tpu_custom_call", operand_layout_constraints={}')
    assert devtrace.label(flash) == ("closed_call.123 custom-call:tpu_custom_call "
                                     "bf16[32,4096,80]")
    loop = "%while.309 = (s32[]{:T(128)}, bf16[1,4096,2560]{1,2,0}) while((s32[]"
    assert devtrace.label(loop) == "while.309 while tuple"
    assert devtrace.is_collective(devtrace.label(
        "%all-reduce-start.3 = f32[8]{0} all-reduce-start(f32[8]{0} %x)"))
    assert devtrace.label("bench.step") == "bench.step"
