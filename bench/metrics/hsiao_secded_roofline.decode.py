"""The Hsiao SEC-DED kernels (encode and scrub) against their memory
roofline (%): the bytes of their operands and results, read from the
shapes in each run's HLO text, over the chip's HBM bandwidth, over their
device time.  Their integer work is not counted, so this is the
bandwidth bound only."""
from bench.trace import hlo_bytes


def read(ctx):
    runs = ctx.trace.op_runs("scrub_hsiao_kernel") \
        + ctx.trace.op_runs("encode_hsiao_kernel")
    if not runs:
        return None
    moved = sum(hlo_bytes(text) for text, _ in runs)
    seconds = sum(d for _, d in runs) * 1e-9
    return 100.0 * moved / ctx.peaks["hbm_bytes_per_s"] / seconds
