"""Model FLOPs of the work delivered in the traced window (prefills of
the admissions and every decoded token at its cache length), over the
window, over the chips' bf16 peak (%)."""


def read(ctx):
    f = ctx.delivered_flops()
    if not f:
        return None
    chips = len(ctx.trace.chips())
    return 100.0 * f / ctx.window.seconds / (chips * ctx.peaks["bf16_flops"])
