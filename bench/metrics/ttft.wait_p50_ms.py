"""ttft.wait_p50_ms: median over the requests due in the window of the
wait for a slot, ``t_admit - t_submit`` from the program's ledger; one not
admitted by the close enters at ``t_close - t_submit``."""
from bench import stats
from bench.ledger import by_close, due_in_window


def read(ctx):
    reqs = due_in_window(ctx.window)
    if reqs is None:
        return None
    t1 = ctx.window.t_close
    waits = [by_close(r.t_admit, t1) - r.t_submit for r in reqs]
    return stats.percentile(waits, 50) * 1e3
