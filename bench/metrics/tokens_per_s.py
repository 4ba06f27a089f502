"""Target tokens of every step finished in the window (global batch x
sequence length) over the window's wall time, input waits included."""


def read(run):
    return run.steps * run.tokens_per_step / run.window_s
