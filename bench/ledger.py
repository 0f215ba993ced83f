"""The program's request ledger joined with the window's stamps.

The population of requests and their due times come from the benchmark's
own stamps, as the end-to-end metrics take them; what happened to each
request inside the program comes from its ledger entry
(``repro.serve.batcher.Request``): when a slot took it (``t_admit``), the
serving-loop iterations at which it was admitted and had its whole prompt
in (``admit_step``, ``ready_step``), and the prompt chunks run for it
(``prefill_chunks``). A program whose ledger lacks these fields gives
nothing to read.
"""
from __future__ import annotations


def due_in_window(window):
    """[ledger entry] of the requests due in [t_open, t_close), in the
    stamps' order; None when there are none or the ledger has no admission
    fields."""
    requests = getattr(window, "requests", None) or {}
    t0, t1 = window.t_open, window.t_close
    out = [requests[s.uid] for s in window.stamps
           if t0 <= s.due < t1 and s.uid in requests]
    if not out or not hasattr(out[0], "t_admit"):
        return None
    return out


def by_close(t, t_close: float) -> float:
    """A stamp as the window saw it: one missing or after the close enters
    at the close."""
    return t_close if t is None or t > t_close else t
