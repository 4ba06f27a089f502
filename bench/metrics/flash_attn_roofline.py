"""Share of the bf16 peak, in percent, that the splash-attention kernels
reach on the step's useful attention: q k^T and p v of every attention,
three forward passes a step (``bench/flops/attention.py``), over the device
time of every op whose name holds ``splash_mha`` (forward, the remat's
recomputed forward and the fused backward) x the chip's bf16 peak.  None
where the traced window holds no such kernel."""
from bench import devtrace
from bench.flops import attention

KERNEL = "splash_mha"


def read(run):
    tr = run.trace
    if tr is None or not tr["devices"] or not devtrace.steps(tr):
        return None
    calls, ns = devtrace.kernel_time(tr, KERNEL)
    if not calls or not ns:
        return None
    rows = run.traffic["batch_per_chip"] * run.chips
    ops = attention.step(run.config, rows, run.traffic["seq_len"]) * devtrace.steps(tr)
    return 100.0 * ops / (ns * 1e-9 * run.peak["bf16_flops"])
