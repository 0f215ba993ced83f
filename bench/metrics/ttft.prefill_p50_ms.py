"""ttft.prefill_p50_ms: median over the requests due in the window and
admitted by its close of the time from a slot taking the request to its
first token, ``t_first - t_admit`` from the program's ledger (its prompt
chunks, waits behind other slots' chunks, and the token sync); one with no
first token by the close enters at ``t_close - t_admit``."""
from bench import stats
from bench.ledger import by_close, due_in_window


def read(ctx):
    reqs = due_in_window(ctx.window)
    if reqs is None:
        return None
    t1 = ctx.window.t_close
    times = [by_close(r.t_first, t1) - r.t_admit for r in reqs
             if r.t_admit is not None and r.t_admit <= t1]
    v = stats.percentile(times, 50)
    return None if v is None else v * 1e3
