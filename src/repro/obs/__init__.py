"""Serving observability: tracing, metrics, and the static collective audit.

Performance work on the serving path needs *measured feedback*; this
package is the one place the serving stack reports itself.

Module map::

    trace.py        Tracer — low-overhead span API threaded through the
                    ServeEngine entry points, stream_serve and the
                    SlotBatcher refill path; each enabled span is also a
                    jax.profiler.TraceAnnotation, so a profiler trace
                    carries the program's spans beside the device ops
                    (device time comes from that trace: the tracer never
                    blocks); Chrome trace-event JSON export viewable in
                    Perfetto; validate_trace / `python -m repro.obs.trace`
                    schema + span-coverage checker (CI runs it).
    metrics.py      MetricsRegistry — process-local counters / gauges /
                    histograms (tok/s, TTFT, per-step latency, queue
                    depth, slot occupancy, ensemble vote agreement and
                    abstains) with numpy-exact p50/p95/p99 summaries,
                    lossless JSON round-trip and Prometheus text export.
    collectives.py  audit_engine — walks the compiled SPMD HLO of the
                    jitted decode_step / prefill_into (via
                    core/hlo_analysis) and reports the exact per-step
                    count + operand bytes of every collective kind plus
                    resharding copies; predict_row_collective feeds the
                    plan_report "collectives" column; golden-gated in CI
                    (benchmarks/check_collectives.py).

Entry points: ``launch.serve --trace out.json --metrics-out m.json
--audit-collectives``; ``stream_serve(..., metrics=registry)``;
``ServeEngine(..., tracer=Tracer())``. See docs/OBSERVABILITY.md for the
span taxonomy, metric names/units, and how to read the audit.
"""
from repro.obs.collectives import (CollectiveAudit, audit_engine, audit_hlo,
                                   format_audit, predict_row_collective)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               record_request_metrics)
from repro.obs.trace import NULL_TRACER, Tracer, validate_trace

__all__ = [
    "CollectiveAudit", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "NULL_TRACER", "Tracer", "audit_engine", "audit_hlo", "format_audit",
    "predict_row_collective", "record_request_metrics", "validate_trace",
]
