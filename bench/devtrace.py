"""From a profiler trace to the numbers the benchmark reads.

Two steps.  ``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
and keeps two things: each device's operations (from the plane's
``XLA Ops`` line, with the ``Async XLA Ops`` line's in-flight copies and
collectives) and the benchmark's own host spans (names starting
``bench.``).  Device and host events share the profiler's clock.  On a TPU
an operation's event name is its whole HLO instruction; ``label`` cuts it
to ``<name> <opcode>[:<custom-call target>] <output type>``.  The result is
plain JSON, so a small recorded trace can be committed and the reduction
tested on it.  Everything else here reduces that JSON.

Operations nest (a ``while`` holds the operations of its body), so busy
time is the union of intervals and an operation's own time is its
duration less its children's.  The window is the host span
``bench.window``; device time outside it is dropped.  A collective is an
operation whose opcode starts with one of ``COLLECTIVES``.
"""
from __future__ import annotations

import glob
import re
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def label(hlo: str) -> str:
    """``%fusion.7 = bf16[8,128]{1,0} fusion(...), ...`` ->
    ``fusion.7 fusion bf16[8,128]``; a name that is no HLO text stays."""
    if " = " not in hlo:
        return hlo
    name, rest = hlo.split(" = ", 1)
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else "?"
    if opcode == "custom-call":
        t = _TARGET.search(rest)
        opcode += ":" + (t.group(1) if t else "?")
    out = "tuple" if rest.startswith("(") else rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{name.lstrip('%')} {opcode} {out}"


def opcode(lab: str) -> str:
    parts = lab.split(" ")
    return parts[1] if len(parts) > 1 else lab


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    devices: Dict[str, dict] = {}
    host: list = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/device:") and not plane.name.startswith("/device:CPU"):
            lines = {line.name: [[label(e.name), e.start_ns, e.duration_ns]
                                 for e in line.events]
                     for line in plane.lines if line.name in (OPS_LINE, ASYNC_LINE)}
            if lines.get(OPS_LINE):
                devices[plane.name] = {"ops": lines[OPS_LINE],
                                       "async": lines.get(ASYNC_LINE, [])}
        elif plane.name.startswith("/host:"):
            host += [[e.name, e.start_ns, e.duration_ns]
                     for line in plane.lines for e in line.events
                     if e.name.startswith("bench.")]
    return {"devices": devices, "host": sorted(host, key=lambda s: s[1])}


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(iv: List[Interval]) -> float:
    return sum(b - a for a, b in iv)


def clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def minus(a_iv: List[Interval], b_iv: List[Interval]) -> List[Interval]:
    """Parts of the (merged) intervals ``a_iv`` not covered by ``b_iv``."""
    out, b_iv, j = [], union(b_iv), 0
    for a, b in union(a_iv):
        cur = a
        while j < len(b_iv) and b_iv[j][1] <= cur:
            j += 1
        k = j
        while k < len(b_iv) and b_iv[k][0] < b:
            if b_iv[k][0] > cur:
                out.append((cur, b_iv[k][0]))
            cur = max(cur, b_iv[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def window(tr: dict) -> Optional[Interval]:
    for name, start, dur in tr["host"]:
        if name == "bench.window":
            return (start, start + dur)
    return None


def steps(tr: dict) -> int:
    lo, hi = window(tr)
    return sum(1 for n, s, d in tr["host"] if n == "bench.step" and lo <= s < hi)


def is_collective(lab: str) -> bool:
    return opcode(lab).startswith(COLLECTIVES)


def intervals(events: list, lo: float, hi: float, keep=lambda lab: True) -> List[Interval]:
    return clip([(s, s + d) for lab, s, d in events if keep(lab)], lo, hi)


def per_device(tr: dict) -> Dict[str, dict]:
    """Busy, collective and exposed-collective ns of each device in the
    window.  Collective time counts collectives on either line (an async
    one is in flight on the async line); exposed time is the part of it in
    which no other operation runs."""
    lo, hi = window(tr)
    out = {}
    for dev, d in tr["devices"].items():
        busy = union(intervals(d["ops"], lo, hi))
        coll = union(intervals(d["ops"] + d["async"], lo, hi, is_collective))
        compute = intervals(d["ops"], lo, hi, lambda lab: not is_collective(lab))
        out[dev] = {"busy": length(busy), "collective": length(coll),
                    "exposed": length(minus(coll, compute)), "window": hi - lo}
    return out


def self_times(ops: list, lo: float, hi: float) -> Dict[str, float]:
    """Own ns of each label in the window: duration less the durations of
    the operations nested directly inside it."""
    own: Dict[str, float] = {}
    stack: list = []                          # [label, end]
    for lab, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        if not (lo <= s < hi):
            continue
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1][0]] = own.get(stack[-1][0], 0.0) - d
        own[lab] = own.get(lab, 0.0) + d
        stack.append([lab, s + d])
    return own


def kernel_time(tr: dict, pattern: str) -> Tuple[int, float]:
    """(calls, ns) of device operations whose label matches ``pattern`` in
    the window, summed over devices."""
    lo, hi = window(tr)
    rx = re.compile(pattern)
    n, t = 0, 0.0
    for d in tr["devices"].values():
        for lab, s, dur in d["ops"]:
            if rx.search(lab) and lo <= s < hi:
                n, t = n + 1, t + dur
    return n, t


def breakdown(tr: dict, top: int = 10) -> dict:
    """The operations with most own device time (seconds per device), and
    the longest idle gaps, each named by the host span around its middle."""
    lo, hi = window(tr)
    n_dev = max(len(tr["devices"]), 1)
    by_op: Dict[str, float] = {}
    gaps = []
    spans = [(n, s, s + d) for n, s, d in tr["host"] if n != "bench.window"]
    for d in tr["devices"].values():
        for lab, t in self_times(d["ops"], lo, hi).items():
            by_op[lab] = by_op.get(lab, 0.0) + t
        for a, b in minus([(lo, hi)], intervals(d["ops"], lo, hi)):
            mid = (a + b) / 2
            host = [n for n, s, e in spans if s <= mid < e]
            gaps.append((host[-1] if host else "bench.none", (b - a) * 1e-9))
    ops_top = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v * 1e-9 / n_dev] for k, v in ops_top],
            "idle_gaps": [list(g) for g in sorted(gaps, key=lambda g: -g[1])[:top]]}
