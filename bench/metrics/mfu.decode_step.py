"""mfu.decode_step: the decode-only serving program's least time at the
chip's peaks (packed weights and ``lm_head`` read once, live K/V rows, the
live slots' projections, attention and logits) over its summed device
time."""
from bench.trace.lm_calls import share


def read(ctx):
    return share(ctx, ["decode_step"])
