"""mfu.lm_window: the decode work of every serving call traced (weights
read once per call, the live slots' tokens), at the chip's peaks, over the
traced window: a share of the chip's peak that idle time lowers too."""
from bench.trace.lm_calls import work
from bench.work.roofline import least_seconds, share_percent


def read(ctx):
    tr = ctx.trace
    least = 0.0
    for name in ("decode_step", "decode_prefill"):
        for s in tr.host_spans(name):
            st = dict(s.stats or {}, span=name)
            if int(st.get("n_live", 0)) > 0:
                least += least_seconds(work(ctx.model, st, decode_only=True),
                                       ctx.peaks)[0]
    return share_percent(least, tr.window_s)
