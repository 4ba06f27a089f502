"""The training step's own device time per layer, from the program's named
scopes.

The program names the parts of its compiled step with ``jax.named_scope``
(``repro.utils.hlo.SCOPES``), and ``hlo.layers`` maps each instruction of
a compiled module to a layer: ``forward``, ``backward``, ``exchange.pack``,
``exchange.bucket``, ``exchange.unpack``, ``optimizer``, ``mixed`` (a
fusion of several) or ``none`` (no scope).  A traced op's label starts with
its instruction name (``devtrace.label``), so an op's layer is looked up by
name in the map of the compiled step.  That is the one live executable
whose module is ``jit_train_step``: the harness's ``cell.compiled`` keeps
it alive while the readers run.  An op the map does not hold counts as
``none``.

``times(run)`` is None, and every reader with it, where the run has no
trace or no device in it, where the process holds no such executable or
more than one, where the program has no ``hlo.layers`` (one older than
the scopes), or where the executable records no scope.
"""
from __future__ import annotations

from typing import Dict, Optional

from bench import devtrace

MODULE = "jit_train_step"
UNATTRIBUTED = ("mixed", "none")

_last: list = [None, None]     # the last trace read, and its times


def module_text() -> Optional[str]:
    """HLO text of the one live executable named ``MODULE``, else None."""
    import jax
    texts = [m.to_string() for e in jax.devices()[0].client.live_executables()
             for m in e.hlo_modules() if m.name == MODULE]
    return texts[0] if len(texts) == 1 else None


def layer_map() -> Optional[Dict[str, str]]:
    """Instruction name -> layer of the compiled step, else None; None too
    where no instruction records a scope (an executable compiled from a
    program without them, read back from a compilation cache)."""
    from repro.utils import hlo
    if not hasattr(hlo, "layers"):
        return None
    text = module_text()
    layer_of = None if text is None else hlo.layers(text)
    if not layer_of or set(layer_of.values()) <= set(UNATTRIBUTED):
        return None
    return layer_of


def per_step_ms(tr: dict, layer_of: Dict[str, str]) -> Dict[str, float]:
    """Own device ms per traced step of each layer, averaged over devices:
    each op's own time in the window (``devtrace.self_times``) goes to the
    layer of its instruction."""
    from repro.utils import hlo
    lo, hi = devtrace.window(tr)
    out = dict.fromkeys(hlo.LAYERS, 0.0)
    for d in tr["devices"].values():
        for lab, ns in devtrace.self_times(d["ops"], lo, hi).items():
            out[layer_of.get(lab.split(" ", 1)[0], "none")] += ns
    scale = 1e-6 / (devtrace.steps(tr) * len(tr["devices"]))
    return {k: v * scale for k, v in out.items()}


def times(run) -> Optional[Dict[str, float]]:
    """{layer: own device ms per traced step} of ``run``'s trace, or None."""
    tr = run.trace
    if tr is None or not tr["devices"] or not devtrace.steps(tr):
        return None
    if _last[0] is not tr:           # the five readers read one trace
        layer_of = layer_map()
        _last[:] = tr, None if layer_of is None else per_step_ms(tr, layer_of)
    return _last[1]
