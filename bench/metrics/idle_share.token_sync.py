"""idle_share.token_sync: the first chip's idle time inside the program's
``token_sync`` host spans (the device->host crossing of sampled tokens),
over the traced window. Host spans are put on the device clock by the
trace's skew, as ``Trace.idle_by_host_span`` does."""


def read(ctx):
    tr = ctx.trace
    spans = sorted((e.start + tr.skew, e.end + tr.skew)
                   for e in tr.host if e.name == "token_sync")
    if not spans:
        return None
    # gaps and spans are each sorted and disjoint: one merge pass
    idle, i = 0.0, 0
    for gs, ge in tr.idle_gaps():
        while i < len(spans) and spans[i][1] <= gs:
            i += 1
        j = i
        while j < len(spans) and spans[j][0] < ge:
            idle += min(ge, spans[j][1]) - max(gs, spans[j][0])
            j += 1
    share = 100.0 * idle * 1e-9 / tr.window_s
    return share, f"{len(spans)} token_sync spans, {idle * 1e-9:.6f} s idle"
