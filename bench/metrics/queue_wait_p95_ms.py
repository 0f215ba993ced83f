"""queue_wait_p95_ms: p95 over the requests due in the window of the time
from a request's due time to a slot taking it (host-clock stamps of the
batcher's refills; one still queued at the close enters at its age)."""
from bench import stats


def read(ctx):
    w = ctx.window
    v = stats.percentile(stats.queue_wait_samples(w.stamps, w.t_open,
                                                  w.t_close), 95)
    return None if v is None else v * 1e3
