"""The trace reduction, checked on a trace recorded on a TPU v5e: 0.36 s of
the chat cell's decode steps (16 slots), written by
``bench/tools/dump_trace.py``. Expected values are recomputed here from
the raw file by other means (a sweep for the union, a scan for the kernel
sums), plus what was read off the trace by hand."""
import os

import pytest

from bench import harness
from bench.trace.reduce import Trace, is_container, op_label
from bench.work.roofline import load_peaks

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "chat_decode.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return Trace(FIXTURE)


@pytest.fixture(scope="module")
def raw():
    """(device ops [(start, end, name)], window (start, end)) read straight
    from the file."""
    from jax.profiler import ProfileData

    ops, win = [], None
    for plane in ProfileData.from_file(FIXTURE).planes:
        for line in plane.lines:
            for e in line.events:
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
                if e.name == "bench_window":
                    win = (e.start_ns, e.start_ns + e.duration_ns)
    return ops, win


def test_busy_is_the_union_of_device_op_intervals(trace, raw):
    ops, (t0, t1) = raw
    points = []
    for s, e, _ in ops:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            points += [(s, 1), (e, -1)]
    points.sort()
    depth, busy, last = 0, 0.0, None
    for t, d in points:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    assert trace.window_s == pytest.approx((t1 - t0) * 1e-9)
    assert trace.busy_s() == pytest.approx(busy * 1e-9, rel=1e-12)
    # read by hand: the chip was busy about nine tenths of the window
    assert 0.85 < trace.busy_s() / trace.window_s < 0.95


def test_kernel_sums(trace, raw):
    ops, (t0, t1) = raw
    want = [e - s for s, e, n in ops
            if s >= t0 and e <= t1 and n.startswith("%_binary_matmul.")
            and "custom-call(" in n]
    got = trace.kernels("_binary_matmul")
    assert len(got) == len(want) > 1000
    assert sum(e.dur for e in got) == pytest.approx(sum(want))
    # every call is a decode projection at M = 16 slots
    shapes = {(e.name.split("custom-call(bf16[")[1].split("]")[0])
              for e in got}
    assert shapes == {"16,3072", "16,12288"}


def test_top_ops_leave_out_container_ops(trace):
    top = trace.top_ops(10)
    assert top[0][0] == "_binary_matmul"
    assert all(not is_container(n) for n, _ in top)
    assert op_label("%while.1 = (s32[]) while((s32[]) %t), "
                    "condition=%c, body=%b") == "while"
    assert is_container("%while.1 = (s32[]) while((s32[]) %t), "
                        "condition=%c, body=%b")
    assert not is_container('%_binary_matmul.3 = f32[16,3072] custom-call('
                            'bf16[16,3072] %x), custom_call_target="t"')


def test_idle_gaps_by_host_span_cover_the_idle_time(trace):
    gaps = trace.idle_by_host_span(100)
    idle = trace.window_s - trace.busy_s()
    assert sum(s for _, s in gaps) == pytest.approx(idle, rel=1e-9)
    # read by hand: the chip waits mostly while the host blocks on the
    # sampled token (np.asarray) and dispatches the next step
    assert gaps[0][0] == "np.asarray(jax.Array)"


def test_device_executions_pair_with_their_host_spans(trace):
    calls = trace.calls("decode_step", "jit__decode_fn")
    assert len(calls) == len(trace.modules("jit__decode_fn")) == 17
    for span, x in calls:
        assert 1 <= span.stats["n_live"] <= 16
        assert x.start - trace.skew >= span.start
    assert len({id(s) for s, _ in calls}) == len(calls)


def test_readers_on_the_recorded_trace(trace):
    cell = harness.load_cell("starcoder2_3b-det.chat")
    ctx = harness.MetricContext(cell.name, cell.config["model"],
                                cell.traffic, load_peaks("TPU v5 lite"),
                                None, trace)

    def read(name):
        got = harness.load_module(f"bench/metrics/{name}.py").read(ctx)
        return got[0] if isinstance(got, tuple) else got

    for name in ("mfu.decode_step", "binary_matmul_roofline",
                 "idle_share.lm", "mfu.lm_window"):
        assert 0.0 < read(name) <= 100.0, name
    # no prompt chunk ran in this recording: the reader reports nothing
    assert read("mfu.prefill_step") is None
