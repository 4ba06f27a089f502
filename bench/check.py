"""The numbers that decide ``correct``: the program's three checked steps
against the reference's.

- ``loss``: the largest |L - L_ref| / |L_ref| over the steps;
- ``grad_norm``: the same of the first step's global gradient norm before
  clipping, which sees a gradient of the wrong scale that clipping hides
  from AdamW (later steps' norms swing with the loss and are not steady);
- ``grad_leaf``: over leaves, the largest gap between the norms of the
  first step's clipped gradient as AdamW holds it, against the reference's
  norm of that leaf or of the median leaf, whichever is larger;
- ``update_leaf``: the same of the change of every leaf over the steps,
  over the leaves whose raw reference gradient is at least a thousandth of
  the median leaf's (a leaf with none moves under AdamW by round-off).

A cell's limits file gives each number it compares a limit, and names each
number it does not compare, with the reason (no control or fault reads
apart from the sound runs); a number that is in neither fails.
"""
from __future__ import annotations

import numpy as np

NAMES = ("loss", "grad_norm", "grad_leaf", "update_leaf")
MOVES_RULE = 1e-3


def _scalar_gap(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.abs(b)))


def _leaf_gap(a, b, keep=None) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if keep is not None:
        a, b = a[keep], b[keep]
    return float(np.max(np.abs(a - b) / np.maximum(b, np.median(b))))


def numbers(prog: dict, ref: dict) -> dict:
    raw = np.asarray(ref["raw_grad_leaf"])
    keep = raw >= MOVES_RULE * np.median(raw)
    return {"loss": _scalar_gap(prog["losses"], ref["losses"]),
            "grad_norm": _scalar_gap(prog["grad_norms"][:1], ref["grad_norms"][:1]),
            "grad_leaf": _leaf_gap(prog["grad_leaf"], ref["grad_leaf"]),
            "update_leaf": _leaf_gap(prog["update_leaf"], ref["update_leaf"], keep)}


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """Each compared number beside its limit (None where it is not finite,
    or has no limit: either fails).  ``limits``: a limits file."""
    skip = limits.get("not_compared", {})
    return {k: {"value": v if np.isfinite(v) else None,
                "limit": limits.get("limits", {}).get(k)}
            for k, v in numbers(prog, ref).items() if k not in skip}


def passed(checks: dict) -> bool:
    return all(c["value"] is not None and c["limit"] is not None
               and c["value"] <= c["limit"] for c in checks.values())
