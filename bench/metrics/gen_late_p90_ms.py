"""How late the load generator sent: 90th percentile of send - due (ms)."""
from bench.context import p90


def read(ctx):
    return p90([(s.sent - s.due) * 1e3 for s in ctx.due_in_window()])
