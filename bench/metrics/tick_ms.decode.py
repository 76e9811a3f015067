"""Device time of one run of the scheduler's tick program (ms, trace)."""
import numpy as np


def read(ctx):
    runs = ctx.trace.program_runs("tick")
    return float(np.mean(runs)) * 1e-6 if runs else None
