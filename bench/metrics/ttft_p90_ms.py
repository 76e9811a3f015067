"""90th percentile of time to first token over every request due in the
window, from its due time (host clock)."""
from bench.context import p90


def read(ctx):
    v = p90([(s.first - s.due) * 1e3 for s in ctx.due_in_window()])
    return v
