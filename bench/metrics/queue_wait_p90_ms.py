"""Admission queue: 90th percentile of due time -> admission launch (ms)."""
from bench.context import p90


def read(ctx):
    return p90([(s.admit_launch - s.due) * 1e3
                for s in ctx.due_in_window()])
