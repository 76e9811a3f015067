"""Model FLOPs of the tokens decoded by the ticks in the traced window,
each at its cache length, over the window, over the chips' bf16 peak
(%).  The admissions' prefills are `mfu.prefill`'s, not this one's."""


def read(ctx):
    f = ctx.delivered_flops(prefill=False)
    if not f:
        return None
    chips = len(ctx.trace.chips())
    return 100.0 * f / ctx.window.seconds / (chips * ctx.peaks["bf16_flops"])
