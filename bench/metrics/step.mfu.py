"""Model FLOP/s utilization of the whole step, in percent: the operations
a step needs (bench/flops, three forward passes, no recomputation) times
the steps of the untraced window, over its seconds x chips x the chip's
bf16 peak."""


def read(run):
    return (100.0 * run.flops_per_step * run.steps
            / (run.window_s * run.chips * run.peak["bf16_flops"]))
