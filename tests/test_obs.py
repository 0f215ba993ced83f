"""Observability: tracer span/event schema, disabled-tracer no-op
guarantees, histogram percentiles vs numpy, metrics export round-trips,
and the traced serving-loop integration (span coverage + ledger-derived
metrics)."""
import json
import time

import numpy as np
import pytest

from repro.obs.metrics import (Counter, Histogram, MetricsRegistry,
                               record_request_metrics)
from repro.obs.trace import _NULL_SPAN, NULL_TRACER, Tracer, validate_trace


class TestHistogram:
    def test_percentiles_match_numpy_quantiles(self):
        """The promised contract: percentile(q) is np.quantile's default
        linear interpolation, bit-for-bit."""
        rng = np.random.default_rng(0)
        xs = rng.gamma(2.0, 3.0, size=257)
        h = Histogram("h")
        for x in xs:
            h.observe(float(x))
        for q in (0, 25, 50, 90, 95, 99, 100):
            assert h.percentile(q) == pytest.approx(
                float(np.quantile(xs, q / 100.0)), rel=1e-12)
        s = h.summary()
        assert s["count"] == 257
        assert s["p50"] == h.percentile(50)
        assert s["p95"] == h.percentile(95)
        assert s["p99"] == h.percentile(99)
        assert s["min"] == float(xs.min()) and s["max"] == float(xs.max())

    def test_empty_histogram(self):
        h = Histogram("h")
        assert h.percentile(50) is None
        assert h.summary() == {"count": 0}
        assert h.sum == 0.0 and h.count == 0

    def test_counter_rejects_decrease(self):
        c = Counter("c")
        c.inc(2)
        with pytest.raises(ValueError):
            c.inc(-1)
        assert c.value == 2


class TestMetricsRegistry:
    def _populated(self):
        reg = MetricsRegistry()
        reg.counter("serve_tokens_total", "tokens").inc(42)
        reg.gauge("serve_tok_per_s", "throughput").set(316.5)
        h = reg.histogram("serve_step_seconds", "step wall")
        for v in (0.01, 0.02, 0.03, 0.05):
            h.observe(v)
        return reg

    def test_json_round_trip_is_lossless(self):
        reg = self._populated()
        blob = json.dumps(reg.to_json())           # must be JSON-able
        back = MetricsRegistry.from_json(json.loads(blob))
        assert back.to_json() == reg.to_json()
        assert back["serve_step_seconds"].samples == [0.01, 0.02, 0.03, 0.05]

    def test_save_round_trip(self, tmp_path):
        reg = self._populated()
        path = reg.save(str(tmp_path / "m.json"))
        with open(path) as f:
            assert MetricsRegistry.from_json(
                json.load(f)).to_json() == reg.to_json()

    def test_type_conflict_raises(self):
        reg = self._populated()
        with pytest.raises(TypeError):
            reg.gauge("serve_tokens_total")
        with pytest.raises(TypeError):
            reg.histogram("serve_tok_per_s")

    def test_get_or_create_returns_same_object(self):
        reg = MetricsRegistry()
        assert reg.counter("a") is reg.counter("a")
        assert "a" in reg and "b" not in reg

    def test_prometheus_text(self):
        text = self._populated().to_prometheus()
        assert "# TYPE serve_tokens_total counter" in text
        assert "serve_tokens_total 42" in text
        assert "# TYPE serve_tok_per_s gauge" in text
        assert "# TYPE serve_step_seconds summary" in text
        assert 'serve_step_seconds{quantile="0.5"}' in text
        assert "serve_step_seconds_sum 0.11" in text
        assert "serve_step_seconds_count 4" in text
        assert text.endswith("\n")


class TestTracerDisabled:
    def test_span_is_shared_null_singleton(self):
        """The hot-loop guarantee: a dormant tracer allocates nothing."""
        tr = Tracer(enabled=False)
        s = tr.span("decode_step", step=3)
        assert s is tr.span("other") is _NULL_SPAN
        assert NULL_TRACER.span("x") is _NULL_SPAN
        with s:
            pass
        tr.instant("submit", uid=0)
        assert tr.events == []

    def test_disabled_span_never_makes_a_profiler_annotation(
            self, monkeypatch):
        """A dormant tracer must not touch the profiler: with
        ``TraceAnnotation`` made to raise, its spans still run."""
        import jax

        def boom(*a, **k):
            raise AssertionError("TraceAnnotation made by a dormant tracer")

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
        for tr in (NULL_TRACER, Tracer(enabled=False)):
            s = tr.span("decode_prefill", slot=1, offset=0, c=8)
            assert s is _NULL_SPAN
            with s:
                pass
        with pytest.raises(AssertionError, match="dormant"):
            with Tracer().span("step"):
                pass


class TestTracerAnnotations:
    def test_enabled_span_opens_a_named_annotation(self, monkeypatch):
        """Each enabled span is also a profiler annotation with its name
        and args, entered and exited around the span's body."""
        import jax

        seen = []

        class Recording:
            def __init__(self, name, **args):
                self.name, self.args = name, args

            def __enter__(self):
                seen.append(("enter", self.name, self.args))

            def __exit__(self, *exc):
                seen.append(("exit", self.name, exc[0]))

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
        tr = Tracer()
        with tr.span("step", step=2):
            with tr.span("token_sync"):
                pass
        assert seen == [("enter", "step", {"step": 2}),
                        ("enter", "token_sync", {}),
                        ("exit", "token_sync", None),
                        ("exit", "step", None)]
        assert [e["name"] for e in tr.events] == ["token_sync", "step"]


class TestTracerEvents:
    def _traced(self):
        tr = Tracer(pid=7)
        with tr.span("root", cap=4):
            with tr.span("child", k=1):
                time.sleep(0.002)
            with tr.span("child2"):
                time.sleep(0.001)
        tr.instant("mark", uid=9)
        return tr

    def test_chrome_trace_schema(self):
        trace = self._traced().to_json()
        info = validate_trace(trace)
        assert info["spans"] == 3
        assert info["root"] == "root"
        assert 0.0 < info["coverage"] <= 1.0
        spans = {e["name"]: e for e in trace["traceEvents"]
                 if e.get("ph") == "X"}
        assert spans["root"]["args"]["depth"] == 0
        assert spans["child"]["args"] == {"k": 1, "depth": 1}
        assert spans["child2"]["args"]["depth"] == 1
        for e in spans.values():
            assert e["cat"] == "serve" and e["pid"] == 7
            assert e["dur"] >= 0
        marks = [e for e in trace["traceEvents"] if e.get("ph") == "i"]
        assert len(marks) == 1 and marks[0]["args"] == {"uid": 9}

    def test_sleep_children_dominate_root(self):
        """The two sleeping children should cover nearly all of the root
        span — the same coverage computation the serving gate uses."""
        info = validate_trace(self._traced().to_json())
        assert info["coverage"] >= 0.9

    def test_save_and_file_validation(self, tmp_path):
        path = self._traced().save(str(tmp_path / "t.json"))
        info = validate_trace(path)
        assert info["spans"] == 3 and info["events"] == 5  # +1 meta, +1 mark

    def test_events_sorted_by_ts(self):
        tr = self._traced()
        ts = [e["ts"] for e in tr.to_json()["traceEvents"]
              if e.get("ph") != "M"]
        assert ts == sorted(ts)

    def test_validate_rejects_bad_traces(self):
        with pytest.raises(ValueError, match="traceEvents"):
            validate_trace({"events": []})
        with pytest.raises(ValueError, match="missing 'dur'"):
            validate_trace({"traceEvents": [
                {"name": "a", "ph": "X", "ts": 0, "pid": 1, "tid": 1}]})
        with pytest.raises(ValueError, match="monotonic"):
            validate_trace({"traceEvents": [
                {"name": "a", "ph": "X", "ts": 5, "dur": 1, "pid": 1,
                 "tid": 1},
                {"name": "b", "ph": "X", "ts": 2, "dur": 1, "pid": 1,
                 "tid": 1}]})
        with pytest.raises(ValueError, match="negative"):
            validate_trace({"traceEvents": [
                {"name": "a", "ph": "X", "ts": 0, "dur": -1, "pid": 1,
                 "tid": 1}]})


class TestTracedServing:
    """End-to-end: the traced + metered serving loop on the smoke model."""

    def _serve(self):
        import jax

        from repro.configs import base as cb
        from repro.models import transformer as T
        from repro.serve.batcher import SlotBatcher
        from repro.serve.engine import ServeEngine, stream_serve

        cfg = cb.get_config("starcoder2_3b", smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        tracer = Tracer()
        engine = ServeEngine(cfg, params, tracer=tracer)
        batcher = SlotBatcher(2, 4, tracer=tracer)
        rng = np.random.default_rng(0)
        metrics = MetricsRegistry()
        prompts = [rng.integers(0, cfg.vocab_size, 4) for _ in range(4)]
        for p in prompts[:2]:
            batcher.submit(p, 3)

        def arrivals(it):
            # the other two arrive while the first two decode
            if it == 2:
                for p in prompts[2:]:
                    batcher.submit(p, 3)
            return it < 2

        steps = stream_serve(engine, batcher, max_new_cap=3, metrics=metrics,
                             arrivals=arrivals)
        return tracer, metrics, batcher, steps

    def test_trace_covers_serving_loop(self):
        tracer, metrics, batcher, steps = self._serve()
        info = validate_trace(tracer.to_json())
        assert info["root"] == "stream_serve"
        assert info["coverage"] >= 0.95   # the acceptance bar CI enforces
        names = {e["name"] for e in tracer.events}
        assert {"stream_serve", "init_decode", "arrivals", "step", "refill",
                "prefill_into", "decode_step", "sample", "token_sync",
                "record", "submit", "slot_refill",
                "request_done"} <= names
        # no fencing: the tracer never splits an enqueue from device time
        assert not names & {"dispatch", "device"}
        depth = {e["name"]: e["args"]["depth"] for e in tracer.events
                 if e.get("ph") == "X"}
        assert depth["arrivals"] == depth["step"] == 1
        assert depth["token_sync"] == 3          # step > sample > token_sync

        # ledger-derived metrics agree with the batcher ground truth
        assert metrics.counter("serve_steps_total").value == steps
        assert (metrics.counter("serve_tokens_total").value
                == batcher.tokens_generated == 12)
        assert metrics.counter("serve_requests_completed_total").value == 4
        assert metrics.counter("serve_prefills_total").value == 4
        assert metrics.histogram("serve_ttft_seconds").count == 4
        assert metrics.histogram("serve_step_seconds").count == steps
        assert metrics.gauge("serve_tok_per_s").value > 0
        occ = metrics.histogram("serve_slot_occupancy")
        assert occ.count == steps and max(occ.samples) <= 1.0


class TestProfilerTrace:
    """The program's spans on the profiler's clock: a chunked-prefill
    serving loop run under ``jax.profiler`` leaves its spans, with their
    args as stats, on the host plane of the ``.xplane.pb``."""

    def test_spans_land_on_the_host_plane(self, tmp_path):
        import glob

        import jax
        from jax.profiler import ProfileData

        from repro.configs import base as cb
        from repro.models import transformer as T
        from repro.serve.batcher import SlotBatcher
        from repro.serve.engine import ServeEngine, stream_serve

        cfg = cb.get_config("starcoder2_3b", smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        tracer = Tracer()
        engine = ServeEngine(cfg, params, tracer=tracer)
        batcher = SlotBatcher(2, 16)
        rng = np.random.default_rng(1)
        batcher.submit(rng.integers(1, cfg.vocab_size, 16), 4)
        sent = []

        def arrivals(it):
            # a second prompt arrives while the first decodes, so its
            # chunks run fused into decode steps (decode_prefill)
            if not sent and batcher.active_mask().any():
                batcher.submit(rng.integers(1, cfg.vocab_size, 16), 2)
                sent.append(it)
            return not sent

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            stream_serve(engine, batcher, max_new_cap=4, prefill_chunk=8,
                         arrivals=arrivals)
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        host = {}
        for plane in ProfileData.from_file(path).planes:
            if plane.name == "/host:CPU":
                for line in plane.lines:
                    for e in line.events:
                        host.setdefault(e.name, []).append(dict(e.stats))
        assert {"stream_serve", "step", "arrivals", "refill", "sample",
                "token_sync", "record", "decode_prefill",
                "prefill_chunk"} <= set(host)
        chunks = sorted((int(st["offset"]), int(st["c"]))
                        for st in host["decode_prefill"])
        assert chunks == [(0, 8), (8, 8)]
        # the in-memory export saw the same spans
        assert len(host["token_sync"]) == sum(
            e["name"] == "token_sync" for e in tracer.events)


class TestRecordRequestMetrics:
    def test_folds_completed_ledger(self):
        from repro.serve.batcher import Request

        class FakeBatcher:
            completed = [
                Request(0, np.zeros(2, np.int32), 2, generated=[1, 2],
                        t_submit=0.0, t_first=0.5, t_done=1.5),
                Request(1, np.zeros(2, np.int32), 1, generated=[3],
                        truncated=True, t_submit=1.0, t_first=1.2,
                        t_done=1.2, agreement=[0.5], abstained=True),
            ]

        reg = MetricsRegistry()
        record_request_metrics(reg, FakeBatcher())
        assert reg.counter("serve_requests_completed_total").value == 2
        assert reg.counter("serve_tokens_total").value == 3
        assert reg.counter("serve_prompts_truncated_total").value == 1
        assert reg.counter("serve_abstain_total").value == 1
        assert reg.histogram("serve_ttft_seconds").samples \
            == pytest.approx([0.5, 0.2])
        assert reg.histogram("serve_request_latency_seconds").samples \
            == pytest.approx([1.5, 0.2])
        assert reg.histogram("serve_vote_agreement").samples == [0.5]
