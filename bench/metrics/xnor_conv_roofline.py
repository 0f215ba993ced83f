"""xnor_conv_roofline: the least time at the chip's peaks of the XNOR conv
and FC layers' work over the summed device time of the sign/patch-pack and
XNOR-popcount matmul kernel events, inside complete forwards."""
from bench.systems.classifier import FORWARD
from bench.work import vgg
from bench.work.roofline import least_seconds, share_percent

#: the kernels' labels in the trace (their HLO instructions are named
#: after the jitted wrappers that call them)
KERNELS = ("sign_and_pack_patches", "sign_and_pack", "_xnor_matmul_packed")


def read(ctx):
    tr = ctx.trace
    execs = tr.modules(FORWARD)
    if not execs:
        return None
    kern = [e for k in KERNELS for e in tr.kernels(k)]
    inside = [e for e in kern
              if any(x.start <= e.start and e.end <= x.end for x in execs)]
    dev = sum(e.dur for e in inside) * 1e-9
    least, bound = least_seconds(
        vgg.forward(ctx.model, int(ctx.traffic["batch"]),
                    kinds=("xnor", "xnor_conv")), ctx.peaks)
    pct = share_percent(least * len(execs), dev)
    return None if pct is None else (pct, f"{bound}-bound, {len(inside)} "
                                          f"kernel events in {len(execs)} "
                                          f"forwards")
