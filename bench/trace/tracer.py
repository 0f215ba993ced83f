"""Host spans on the profiler's clock, without touching the program.

``ProfilerTracer`` has the interface the program's serving stack calls
(``span``, ``instant``, ``fence``; see ``repro.obs.trace.Tracer``): each span
opens a ``jax.profiler.TraceAnnotation``, so the program's own spans
(``refill``, ``sample``, ``record``, ``decode_step``, ``decode_prefill``,
``prefill_chunk``, ``dispatch``, ...) land in the trace beside the device
ops. It never fences. ``annotate(name, args)`` may add stats to a span (the
LM driver adds the live rows each serving program works on).
"""
from __future__ import annotations

import contextlib
import os
import shutil
import tempfile


class ProfilerTracer:
    enabled = True
    fence_enabled = False

    def __init__(self, annotate=None):
        self.annotate = annotate

    def span(self, name: str, **args):
        import jax

        if self.annotate is not None:
            args.update(self.annotate(name, args))
        return jax.profiler.TraceAnnotation(name, **args)

    def instant(self, name: str, **args) -> None:
        return None

    def fence(self, value):
        return value


class TraceWindow:
    """Starts the profiler, marks the window with a ``bench_window`` host
    annotation, and on ``stop`` reduces the trace and deletes its files.

    Files go to a fresh directory under ``TMPDIR``; ``keep_dir``, when set,
    receives a copy of the ``.xplane.pb`` (for reading a trace by hand)."""

    keep_dir = None

    def __init__(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._mark = jax.profiler.TraceAnnotation("bench_window")
        self._mark.__enter__()
        self.trace = None

    def stop(self):
        import jax

        from bench.trace.reduce import Trace, find_xplane

        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            path = find_xplane(self.dir)
            if self.keep_dir:
                os.makedirs(self.keep_dir, exist_ok=True)
                shutil.copy(path, self.keep_dir)
            self.trace = Trace(path)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return self.trace

    def abandon(self):
        with contextlib.suppress(Exception):
            import jax

            jax.profiler.stop_trace()
        if os.path.isdir(self.dir):
            shutil.rmtree(self.dir, ignore_errors=True)
