"""Bytes one decode step must read (every weight it multiplies through,
and each slot's live KV at the middle of the chunk, from the shapes),
over the device time of one step (tick time / chunk), over the chip's
HBM bandwidth (%)."""
import numpy as np

from bench import flops as F


def read(ctx):
    runs = ctx.trace.program_runs("tick")
    live = ctx.window.live_kv_tokens
    if not runs or not live:
        return None
    step_s = float(np.mean(runs)) * 1e-9 / ctx.mix["chunk"]
    need = F.decode_step_bytes(ctx.conf, ctx.dtype_bytes,
                               float(np.mean(live)))
    return 100.0 * need / step_s / ctx.peaks["hbm_bytes_per_s"]
