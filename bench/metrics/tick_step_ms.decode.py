"""Device time of the tick's decode scan over the chunk (`step` scope), per
tick run (ms): each op's self time, by the phase the program registered
for its instruction (trace)."""
from bench.scopes import phase_ms


def read(ctx):
    return phase_ms(ctx.trace, "tick", "step")
