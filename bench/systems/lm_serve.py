"""Drives a decoder-LM configuration through the program's serving path.

Build: ``init_lm`` draws the bf16 weights from the seed in one jit,
``compile_plan(params, DEFAULT_POLICY, plan).pack`` packs them, and the bf16
master is released before any cache exists. Serving: ``ServeEngine`` +
``stream_serve`` with the cell's geometry (slots, prompt length, max_new cap,
prefill chunk), greedy, one decode step per iteration.

The window runs inside one ``stream_serve`` call. Its ``arrivals`` hook,
polled once per iteration, submits due requests, opens the window, starts
the profiler for the last ``trace_seconds`` of a traced run, and closes the
window on time by raising ``WindowClosed``: in-flight requests are not
drained into it. A ``SlotBatcher`` subclass stamps every slot refill and
every recorded token on the host clock.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time
from typing import Optional

import numpy as np

from bench import stats
from bench.harness import load_module
from bench.seeds import host_rng, model_key

#: jitted serving programs of ``ServeEngine``: host span -> module name in
#: the device trace
PROGRAMS = {"decode_step": "jit__decode_fn",
            "decode_prefill": "jit__decode_prefill",
            "prefill_chunk": "jit__prefill_chunk"}


class WindowClosed(Exception):
    pass


def make_batcher(n_slots: int, prompt_len: int, tracer):
    from repro.serve.batcher import SlotBatcher

    class StampBatcher(SlotBatcher):
        """``SlotBatcher`` that stamps refills and recorded tokens."""

        def __init__(self):
            super().__init__(n_slots, prompt_len, tracer=tracer)
            self.stamps: dict[int, stats.RequestStamps] = {}
            self.requests: dict = {}

        def submit_due(self, prompt, max_new: int, due: float) -> int:
            uid = self.submit(prompt, max_new)
            self.requests[uid] = self.queue[-1]
            self.stamps[uid] = stats.RequestStamps(uid, due)
            return uid

        def refill(self):
            changed = super().refill()
            now = time.perf_counter()
            for i in changed:
                st = self.stamps.get(self.slots[i].uid)
                if st is not None:
                    st.refill = now
            return changed

        def record(self, tokens, **kw):
            now = time.perf_counter()
            before = [len(r.generated) if r is not None else 0
                      for r in self.slots]
            super().record(tokens, **kw)
            for i, r in enumerate(self.slots):
                if r is not None and len(r.generated) > before[i]:
                    st = self.stamps.get(r.uid)
                    if st is not None:
                        st.tokens.append(now)

    return StampBatcher()


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    stamps: list            # [stats.RequestStamps]
    requests: dict          # uid -> repro.serve.batcher.Request
    trace: Optional[object] = None
    lateness: Optional[list] = None


class System:
    def __init__(self, cfg: dict, seed: int, traced: bool):
        import jax

        from repro.configs.base import ModelConfig
        from repro.core.policy import DEFAULT_POLICY
        from repro.engine import compile_plan
        from repro.models import transformer as T
        from repro.serve.engine import ServeEngine

        self.cfg, self.seed = cfg, seed
        self.model = cfg["model"]
        self.mc = ModelConfig(**self.model)
        self.batcher = None
        tracer = None
        if traced:
            from bench.trace.tracer import ProfilerTracer

            tracer = ProfilerTracer(annotate=self._annotate)
        self.tracer = tracer
        params = T.init_lm(self.mc, model_key(seed),
                           dtype=self.mc.activation_dtype)
        plan = compile_plan(params, DEFAULT_POLICY, cfg["plan"])
        packed = plan.pack(params)
        jax.block_until_ready(packed)
        del params
        gc.collect()
        self.engine = ServeEngine(self.mc, packed, tracer=tracer)

    # -- work stats on the serving programs' host spans (traced runs) -------
    def _annotate(self, name: str, args: dict) -> dict:
        b = self.batcher
        if b is None or name not in PROGRAMS:
            return {}
        live = b.active_mask()
        rows = sum(b.prompt_len + len(r.generated)
                   for r, on in zip(b.slots, live) if on)
        return {"n_live": int(live.sum()), "kv_rows": int(rows)}

    # -- serving ---------------------------------------------------------------
    def _serve(self, batcher, serving: dict, arrivals):
        from repro.serve.engine import stream_serve

        self.batcher = batcher
        try:
            stream_serve(self.engine, batcher,
                         max_new_cap=int(serving["max_new_cap"]),
                         prefill_chunk=int(serving["prefill_chunk"]),
                         arrivals=arrivals)
        except WindowClosed:
            pass
        finally:
            self.batcher = None

    def warm_up(self, traffic: dict) -> None:
        """Compiles (or loads) every program the window uses at the cell's
        geometry: whole-chunk prefill with nothing decoding, the fused
        decode + chunk step, the decode step and the greedy argmax."""
        sv = traffic["serving"]
        b = make_batcher(int(sv["slots"]), int(sv["prompt_len"]), self.tracer)
        rng = host_rng(0, 9)
        vocab = self.model["vocab_size"]

        def prompt():
            return rng.integers(1, vocab, int(sv["prompt_len"]),
                                dtype=np.int32)

        b.submit_due(prompt(), 6, time.perf_counter())
        sent = []

        def arrivals(it):
            if not sent and b.active_mask().any():
                b.submit_due(prompt(), 3, time.perf_counter())
                sent.append(it)
            return not sent

        self._serve(b, sv, arrivals)

    def window(self, traffic: dict, seconds: float, traced: bool) -> Window:
        seed = self.seed
        gen = load_module(f"bench/traffic/{traffic['generator']}.py")
        sv = traffic["serving"]
        b = make_batcher(int(sv["slots"]), int(sv["prompt_len"]), self.tracer)
        vocab = self.model["vocab_size"]
        trace_s = min(float(traffic.get("trace_seconds", seconds)), seconds)
        st = {"open": None, "close": None, "tw": None, "late": []}

        def trace_hook(now):
            if traced and st["tw"] is None and now >= st["close"] - trace_s:
                from bench.trace.tracer import TraceWindow

                st["tw"] = TraceWindow()

        if gen.KIND == "open":
            lead = float(traffic["lead_in_s"])
            sched = gen.schedule(traffic, seed, lead + seconds + 1.0, vocab)
            t0 = time.perf_counter()
            st["open"], st["close"] = t0 + lead, t0 + lead + seconds
            nxt = [0]

            def arrivals(it):
                now = time.perf_counter()
                if now >= st["close"]:
                    raise WindowClosed
                trace_hook(now)
                while nxt[0] < len(sched) and t0 + sched[nxt[0]][0] <= now:
                    off, p, m = sched[nxt[0]]
                    b.submit_due(p, m, t0 + off)
                    st["late"].append(now - (t0 + off))
                    nxt[0] += 1
                return True
        else:
            sessions = gen.session_stream(traffic, seed, vocab)

            def arrivals(it):
                now = time.perf_counter()
                if st["open"] is None and not b.queue and \
                        b.active_mask().all():
                    st["open"], st["close"] = now, now + seconds
                if st["close"] is not None:
                    if now >= st["close"]:
                        raise WindowClosed
                    trace_hook(now)
                free = sum(1 for r in b.slots if r is None or r.done)
                for _ in range(free - len(b.queue)):
                    p, m = next(sessions)
                    b.submit_due(p, m, now)
                return True

        try:
            self._serve(b, sv, arrivals)
        except BaseException:
            if st["tw"] is not None:
                st["tw"].abandon()
            raise
        trace = st["tw"].stop() if st["tw"] is not None else None
        return Window(st["open"], st["close"], list(b.stamps.values()),
                      b.requests, trace, st["late"])

    # -- end-to-end metrics ----------------------------------------------------
    @staticmethod
    def end_to_end(win: Window, traffic: dict) -> tuple[dict, int, int]:
        t0, t1 = win.t_open, win.t_close
        ttft, attempted, failed = stats.ttft_samples(win.stamps, t0, t1)
        itl = stats.itl_samples(win.stamps, t0, t1)
        out = {"itl_p95_ms": _ms(stats.percentile(itl, 95)),
               "tokens_per_s": stats.tokens_in(win.stamps, t0, t1)
               / (t1 - t0)}
        if traffic["generator"] == "open_loop":
            out["ttft_p50_ms"] = _ms(stats.percentile(ttft, 50))
        for name, v in (("ttft", ttft), ("itl", itl)):
            if v:
                print(f"{name}: n={len(v)} p50 "
                      f"{_ms(stats.percentile(v, 50)):.3f} p90 "
                      f"{_ms(stats.percentile(v, 90)):.3f} p95 "
                      f"{_ms(stats.percentile(v, 95)):.3f} max "
                      f"{_ms(max(v)):.3f} ms", file=sys.stderr)
        else:
            attempted = sum(1 for s in win.stamps
                            if any(t0 <= t <= t1 for t in s.tokens))
            failed = 0
        return out, attempted, failed

    def release(self) -> None:
        self.engine = None
        self.batcher = None
        gc.collect()

    # -- correctness -----------------------------------------------------------
    def sample(self, win: Window, traffic: dict):
        """Requests compared with the reference: the one with the most
        served tokens and others drawn from the seed, among those finished
        in the window (in-flight ones when too few finished)."""
        n = int(traffic["check"]["sample_requests"])
        reqs = [r for r in win.requests.values() if r.generated]
        done = [r for r in reqs if r.done]
        pool = done if len(done) >= n else reqs
        pool = sorted(pool, key=lambda r: (-len(r.generated), r.uid))
        if not pool:
            return []
        rest = pool[1:]
        pick = host_rng(self.seed, 3).permutation(len(rest))[: n - 1]
        return [pool[0]] + [rest[i] for i in sorted(pick)]

    def check(self, win: Window, traffic: dict,
              quant: Optional[str] = None) -> dict:
        from bench.reference.compare import lm_readings

        reqs = self.sample(win, traffic)
        print(f"reference: {len(reqs)} requests, "
              f"{sum(len(r.generated) for r in reqs)} served tokens",
              file=sys.stderr, flush=True)
        return lm_readings(self.model, model_key(self.seed), reqs,
                           int(traffic["serving"]["prompt_len"]), quant)


def _ms(v):
    return None if v is None else v * 1e3
