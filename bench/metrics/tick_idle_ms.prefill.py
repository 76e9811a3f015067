"""Device idle time per tick inside the program's own `batcher.tick`
host spans (ms): the idle that the tick's host path leaves.  The
recorder's spans are moved onto the trace's clock by the `bench.tick`
annotations that enclose them (trace and flight recorder)."""
from bench.scopes import tick_idle_ms


def read(ctx):
    return tick_idle_ms(ctx.trace, ctx.window)
