"""90th percentile, over the requests due in the window, of (last token -
first token) / (tokens - 1), in ms (host clock)."""
from bench.context import p90


def read(ctx):
    return p90([(s.last - s.first) * 1e3 / (len(s.tokens) - 1)
                for s in ctx.due_in_window()
                if s.tokens is not None and len(s.tokens) > 1])
