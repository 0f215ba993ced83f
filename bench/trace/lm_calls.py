"""The LM serving programs in a trace, each with the work it was given.

Every call of a serving program opens a host span (``decode_step``,
``decode_prefill``, ``prefill_chunk``) that the LM driver annotates with the
live slots (``n_live``), the cached rows they attend to (``kv_rows``) and the
prefill chunk (``offset``, ``c``). Each execution of the program on the
device is paired with the span open when the host enqueued it.
"""
from __future__ import annotations

from bench.systems.lm_serve import PROGRAMS
from bench.work import lm


def calls(trace, names):
    """[(span stats, device execution)] of the programs of ``names``."""
    out = []
    for name in names:
        out += [(dict(s.stats or {}, span=name), x)
                for s, x in trace.calls(name, PROGRAMS[name])]
    return out


def work(model: dict, st: dict, decode_only: bool = False) -> dict:
    """Work of one call from its span stats (``decode_only``: leave the
    prefill chunk out)."""
    chunk = None
    if not decode_only and int(st.get("c", 0)) > 0:
        chunk = (int(st["offset"]), int(st["c"]))
    n = int(st.get("n_live", 0)) if st["span"] != "prefill_chunk" else 0
    return lm.step(model, n, int(st.get("kv_rows", 0)) if n else 0, chunk)


def share(ctx, names):
    """(percent, note): least time at the peaks of the calls of ``names``
    over their summed device time; None when no call was traced."""
    from bench.work.roofline import least_seconds, share_percent

    pairs = calls(ctx.trace, names)
    if not pairs:
        return None
    least, bounds = 0.0, {}
    for st, _ in pairs:
        s, b = least_seconds(work(ctx.model, st), ctx.peaks)
        least += s
        bounds[b] = bounds.get(b, 0) + 1
    dev = sum(x.dur for _, x in pairs) * 1e-9
    pct = share_percent(least, dev)
    if pct is None:
        return None
    note = (f"{len(pairs)} calls, {dev:.6f} s on the device; bound: "
            + ", ".join(f"{k} in {v}" for k, v in sorted(bounds.items())))
    return pct, note
