"""The readers of the program's request ledger and of its ``token_sync``
spans: hand-made windows and traces with values worked out by hand, a
program without the ledger fields or the spans (nothing to read), and a
CPU-size chat window served through the benchmark's LM system."""
import dataclasses
import os
import types

import pytest

from bench import harness
from bench.stats import RequestStamps
from bench.trace.reduce import DevicePlane, Ev, Trace
from repro.serve.batcher import Request

from bench_helpers import CPU_PEAKS

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "chat_decode.xplane.pb")
#: 0.35 s of the chat cell recorded on a TPU v5e with the program's
#: ``arrivals`` / ``token_sync`` spans and named scopes
#: (``bench/tools/dump_trace.py``, seed 2147484301)
FIXTURE_SYNC = os.path.join(os.path.dirname(__file__), "fixtures",
                            "chat_token_sync.xplane.pb")
LEDGER_METRICS = ("ttft.wait_p50_ms", "ttft.prefill_p50_ms",
                  "prefill.steps_per_chunk")


def read(name, window=None, trace=None):
    got = harness.load_module(f"bench/metrics/{name}.py").read(
        harness.MetricContext("cell", {}, {}, CPU_PEAKS, window, trace))
    return got[0] if isinstance(got, tuple) else got


def req(uid, t_submit, t_admit=None, t_first=None, admit=None, ready=None,
        chunks=0):
    return Request(uid, None, 8, t_submit=t_submit, t_admit=t_admit,
                   t_first=t_first, admit_step=admit, ready_step=ready,
                   prefill_chunks=chunks)


def window(requests, dues):
    return types.SimpleNamespace(
        t_open=10.0, t_close=20.0,
        stamps=[RequestStamps(uid, due) for uid, due in dues.items()],
        requests={r.uid: r for r in requests})


def ledger_window():
    return window([
        req(0, 9.0, 9.1, 9.2, 1, 4, 4),              # due before the window
        req(1, 10.01, 10.05, 10.30, 5, 8, 4),        # chunks back to back
        req(2, 12.0, 12.5, 13.0, 30, 37, 4),         # waited behind another
        req(6, 15.0, 15.1, 15.2, 60, 60, 0),         # whole-prompt cache hit
        req(3, 19.0, 19.2, None, 100, None, 2),      # prompt not in by close
        req(4, 19.5),                                # never admitted
        req(5, 20.0, 20.0, 20.1, 200, 203, 4),       # due at the close
    ], {0: 9.0, 1: 10.0, 2: 12.0, 6: 15.0, 3: 19.0, 4: 19.5, 5: 20.0})


def test_wait_for_a_slot():
    # waits 0.04, 0.5, 0.1, 0.2 and 0.5 (never admitted: close - submit)
    assert read("ttft.wait_p50_ms", ledger_window()) == pytest.approx(200.0)


def test_admission_to_first_token():
    # 0.25, 0.5, 0.1 and 0.8 (no first token: close - admit); request 4
    # was never admitted
    assert read("ttft.prefill_p50_ms", ledger_window()) == pytest.approx(
        375.0)


def test_steps_per_chunk():
    # requests 1, 2 and 6 had their prompt in: (4 + 8 + 1) / (4 + 4 + 0)
    assert read("prefill.steps_per_chunk", ledger_window()) == 13 / 8


def test_ledger_readers_find_nothing_without_the_ledger_fields():
    """A program whose ``Request`` has no admission fields, or a window
    with no request due in it: nothing to read, no error."""
    @dataclasses.dataclass
    class OldRequest:
        uid: int
        t_submit: float
        t_first: float

    old = window([OldRequest(1, 11.0, 11.5)], {1: 11.0})
    empty = window([req(1, 21.0)], {1: 21.0})
    for name in LEDGER_METRICS:
        assert read(name, old) is None, name
        assert read(name, empty) is None, name


def synthetic_trace(skew=-1e7):
    """One second with two idle gaps on the device, 0.2-0.3 s and
    0.6-0.7 s, and host spans given on the host clock."""
    tr = Trace.__new__(Trace)
    tr.t0, tr.t1, tr.skew = 0.0, 1e9, skew
    tr.devices = [DevicePlane("/device:TPU:0", [
        Ev("%a", 0.0, 2e8), Ev("%b", 3e8, 6e8), Ev("%c", 7e8, 1e9)], [])]
    tr.host = sorted([
        Ev("token_sync", 1e8, 1.5e8),      # device busy throughout
        Ev("token_sync", 2.5e8, 3.5e8),    # on the device: 0.24-0.34 s
        Ev("token_sync", 6.2e8, 6.5e8),    # on the device: 0.61-0.64 s
        Ev("record", 6.5e8, 7.1e8),
    ], key=lambda e: e.start)
    return tr


def test_idle_time_inside_token_sync():
    # 0.24-0.30 s of the first gap and 0.61-0.64 s of the second: 0.09 s
    assert read("idle_share.token_sync",
                trace=synthetic_trace()) == pytest.approx(9.0)
    # with no skew: 0.25-0.30 s and 0.62-0.65 s
    assert read("idle_share.token_sync",
                trace=synthetic_trace(0.0)) == pytest.approx(8.0)


def test_token_sync_reader_finds_nothing_in_an_older_trace():
    """The recorded fixture predates the ``token_sync`` span."""
    assert read("idle_share.token_sync", trace=Trace(FIXTURE)) is None


def test_token_sync_on_a_recorded_trace():
    """On a chip-recorded trace the reader agrees with a brute-force
    overlap of every idle gap with every ``token_sync`` span, and the
    labels the other readers key on are still there."""
    tr = Trace(FIXTURE_SYNC)
    names = {e.name for e in tr.host}
    assert {"arrivals", "step", "sample", "token_sync", "record",
            "decode_prefill"} <= names
    assert not names & {"dispatch", "device"}
    syncs = [(e.start + tr.skew, e.end + tr.skew) for e in tr.host
             if e.name == "token_sync"]
    brute = sum(max(0.0, min(ge, se) - max(gs, ss))
                for gs, ge in tr.idle_gaps() for ss, se in syncs)
    got = read("idle_share.token_sync", trace=tr)
    assert got == pytest.approx(100.0 * brute * 1e-9 / tr.window_s,
                                rel=1e-9)
    assert 0.0 < got < read("idle_share.lm", trace=tr)
    # kernel and program labels are those the other readers key on
    assert len(tr.kernels("_binary_matmul")) > 1000
    calls = tr.calls("decode_prefill", "jit__decode_prefill")
    assert len(calls) == len(tr.modules("jit__decode_prefill")) == 7
    assert all(int(s.stats["c"]) == 256 for s, _ in calls)


def test_ledger_readers_on_a_served_window(smoke_root):
    """The program's ledger, filled by a CPU-size chat window through the
    LM system, reads as the definitions say."""
    harness.set_root(smoke_root)
    try:
        cell = harness.load_cell("lm_smoke.chat")
        sysmod = harness.load_module("bench/systems/lm_serve.py")
        system = sysmod.System(cell.config, 11, False)
        system.warm_up(cell.traffic)
        win = system.window(cell.traffic, 1.0, False)
    finally:
        harness.set_root(harness.CHECKOUT)
    reqs = [win.requests[s.uid] for s in win.stamps
            if win.t_open <= s.due < win.t_close]
    assert reqs
    ready = [r for r in reqs if r.ready_step is not None]
    chunk = int(cell.traffic["serving"]["prefill_chunk"])
    prompt_len = int(cell.traffic["serving"]["prompt_len"])
    for r in ready:
        assert r.prefill_chunks == prompt_len // chunk
        assert r.ready_step - r.admit_step + 1 >= r.prefill_chunks
        assert r.t_submit <= r.t_admit
    spc = read("prefill.steps_per_chunk", win)
    assert spc >= 1.0
    assert read("ttft.wait_p50_ms", win) >= 0.0
    assert read("ttft.prefill_p50_ms", win) > 0.0
