"""Every output token marked in the window, over the window's seconds.

The host marks a request's first token when its admission completes and
each tick's fresh tokens per slot when the tick completes; requests still
in flight at the close count the tokens they got in the window.
"""


def read(ctx):
    w = ctx.window
    return w.tokens_in_window() / w.seconds
