"""Device time of one run of an admission program, every bucket (ms)."""
import numpy as np


def read(ctx):
    runs = ctx.trace.program_runs("admit")
    return float(np.mean(runs)) * 1e-6 if runs else None
