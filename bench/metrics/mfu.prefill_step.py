"""mfu.prefill_step: the least time at the chip's peaks of the programs
that carry prompt chunks (the fused decode + chunk step and the chunk-only
step) over their summed device time."""
from bench.trace.lm_calls import share


def read(ctx):
    return share(ctx, ["decode_prefill", "prefill_chunk"])
