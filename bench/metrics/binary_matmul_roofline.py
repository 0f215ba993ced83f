"""binary_matmul_roofline: the least time at the chip's peaks of every
traced ``binary_matmul`` kernel call's work (bf16 (M, K) activations times
packed ±1 (K, N) weights with an f32 scale, f32 out) over the summed device
time of those kernel events. Each call's M, K and N are read off its HLO
instruction in the trace (the shapes the kernel was called with)."""
import re

from bench.work import lm
from bench.work.roofline import least_seconds, share_percent

LABEL = "_binary_matmul"
SHAPES = re.compile(r"= f32\[(\d+),(\d+)\]\S* custom-call\(bf16\[(\d+),(\d+)\]")


def read(ctx):
    least = dev = 0.0
    bounds: dict = {}
    for e in ctx.trace.kernels(LABEL):
        m = SHAPES.search(e.name)
        if m is None:
            continue
        mm, n, _, k = (int(g) for g in m.groups())
        s, b = least_seconds(lm.binary_matmul_call(mm, k, n), ctx.peaks)
        least += s
        dev += e.dur * 1e-9
        bounds[b] = bounds.get(b, 0) + 1
    pct = share_percent(least, dev)
    if pct is None:
        return None
    return pct, ("bound: " + ", ".join(f"{k} in {v} calls"
                                       for k, v in sorted(bounds.items())))
