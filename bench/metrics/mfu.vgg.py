"""mfu.vgg: one forward's least time at the chip's peaks (dense layers at
the bf16 peak, XNOR layers at the int8 peak, each layer's input, output
and weights at HBM bandwidth) over the device time per forward."""
from bench.systems.classifier import FORWARD
from bench.work import vgg
from bench.work.roofline import least_seconds, share_percent


def read(ctx):
    execs = ctx.trace.modules(FORWARD)
    if not execs:
        return None
    least, bound = least_seconds(
        vgg.forward(ctx.model, int(ctx.traffic["batch"])), ctx.peaks)
    dev = sum(x.dur for x in execs) * 1e-9 / len(execs)
    pct = share_percent(least, dev)
    return None if pct is None else (pct, f"{bound}-bound, {len(execs)} "
                                          f"forwards, {dev:.6f} s each")
