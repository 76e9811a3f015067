"""Share of the traced window in which no operation ran on the device (%),
averaged over the chips used."""


def read(ctx):
    busy = ctx.mean_busy_share()
    return None if busy is None else 100.0 * (1.0 - busy)
