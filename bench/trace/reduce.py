"""Reduction of a profiler trace (``.xplane.pb``) to device intervals.

A traced run brackets its window with a host annotation named
``bench_window``. Inside it this module gives:

* the union of the device-op intervals of each chip (``busy``) and so the
  idle share;
* the device ops, grouped by name, that took the most time;
* per-program executions (the ``XLA Modules`` line) and per-kernel events
  (the ``XLA Ops`` line), for the per-layer metric readers;
* the idle gaps of the device, attributed to the innermost host span open
  at each gap's midpoint (what the host was doing while the chip waited).

Only ``jax.profiler.ProfileData`` is used to read the file.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Optional

WINDOW = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"[.:]\d+$")
_INSTR = re.compile(r"%([^ =]+) = ")
_CONTAINER = re.compile(r"[ )](while|conditional|call)\(")


def op_label(name: str) -> str:
    """An op event's name is its HLO instruction text on a TPU; its label
    is the instruction name without the numeric suffix (``_binary_matmul``,
    ``fusion``, ``copy``)."""
    m = _INSTR.match(name)
    return _SUFFIX.sub("", m.group(1) if m else name)


def is_container(name: str) -> bool:
    """A ``while``/``conditional``/``call`` op, whose interval holds the ops
    of its body."""
    head = name.split(", body=")[0]
    return bool(_CONTAINER.search(head))


def is_kernel(name: str, label: str) -> bool:
    """A Pallas kernel call labelled ``label``."""
    return (op_label(name) == label
            and 'custom_call_target="tpu_custom_call"' in name)


@dataclasses.dataclass
class Ev:
    name: str
    start: float          # ns, on the profiler's clock
    end: float
    stats: Optional[dict] = None

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class DevicePlane:
    name: str
    ops: list             # [Ev] of the ops line, sorted by start
    modules: list         # [Ev] of the modules line, sorted by start


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line, with_stats: bool):
    out = []
    for e in line.events:
        s = e.start_ns
        out.append(Ev(e.name, s, s + e.duration_ns,
                      dict(e.stats) if with_stats else None))
    out.sort(key=lambda ev: ev.start)
    return out


class Trace:
    """One traced window: the device planes' op and module events, and the
    host thread that holds the ``bench_window`` annotation, with stats."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
        self.devices: list[DevicePlane] = []
        self.host: list[Ev] = []
        self.enqueued: dict = {}      # flow id -> host time of the enqueue
        window = None
        for plane in pd.planes:
            if DEVICE_PLANE.match(plane.name):
                ops, mods = [], []
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        ops = _events(line, with_stats=False)
                    elif line.name == MODULES_LINE:
                        mods = _events(line, with_stats=True)
                self.devices.append(DevicePlane(plane.name, ops, mods))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    evs = _events(line, with_stats=True)
                    for e in evs:
                        if "_p" in e.stats:
                            self.enqueued[e.stats["_p"]] = e.start
                    if any(e.name == WINDOW for e in evs):
                        self.host = evs
                        window = next(e for e in evs if e.name == WINDOW)
        if window is None:
            raise ValueError(f"{path}: no {WINDOW!r} host annotation")
        if not self.devices:
            raise ValueError(f"{path}: no TPU device plane")
        self.t0, self.t1 = window.start, window.end
        # The device clock and the host clock may disagree by about a
        # millisecond. A program cannot start before the host enqueued it,
        # so the earliest start-after-enqueue bounds the skew.
        lags = [e.start - self.enqueued[e.stats["_c"]]
                for p in self.devices for e in p.modules
                if e.stats.get("_c") in self.enqueued]
        self.skew = min(0.0, min(lags)) if lags else 0.0

    # -- window --------------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def inside(self, evs):
        return [e for e in evs if e.start >= self.t0 and e.end <= self.t1]

    def _busy_intervals(self, plane: DevicePlane):
        merged = []
        for e in plane.ops:
            s, t = max(e.start, self.t0), min(e.end, self.t1)
            if t <= s:
                continue
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], t)
            else:
                merged.append([s, t])
        return merged

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips traced."""
        tot = sum(t - s for p in self.devices
                  for s, t in self._busy_intervals(p))
        return tot * 1e-9 / len(self.devices)

    # -- breakdown -----------------------------------------------------------
    def top_ops(self, n: int = 10):
        """[[label, seconds]] of the device ops that took most time, summed
        over the chips (container ops left out: their bodies count)."""
        tot: dict[str, float] = {}
        for p in self.devices:
            for e in self.inside(p.ops):
                if is_container(e.name):
                    continue
                k = op_label(e.name)
                tot[k] = tot.get(k, 0.0) + e.dur
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in best]

    def idle_gaps(self):
        """[(start, end)] of the first chip's idle intervals in the window."""
        gaps, prev = [], self.t0
        for s, t in self._busy_intervals(self.devices[0]):
            if s > prev:
                gaps.append((prev, s))
            prev = t
        if self.t1 > prev:
            gaps.append((prev, self.t1))
        return gaps

    def host_spans_at(self, times):
        """Name of the innermost (shortest) host span open at each of the
        sorted ``times`` ("none" where none is)."""
        out, active, i, host = [], [], 0, self.host
        for t in times:
            while i < len(host) and host[i].start <= t:
                if host[i].dur > 0 and host[i].name != WINDOW:
                    active.append(host[i])
                i += 1
            active = [e for e in active if e.end >= t]
            out.append(min(active, key=lambda e: e.dur).name
                       if active else "none")
        return out

    def idle_by_host_span(self, n: int = 10):
        """[[host span, idle seconds]]: the idle time of the chip, summed by
        the innermost host span open in the middle of each gap."""
        gaps = self.idle_gaps()
        names = self.host_spans_at([0.5 * (s + t) - self.skew
                                    for s, t in gaps])
        tot: dict[str, float] = {}
        for (s, t), k in zip(gaps, names):
            k = _SUFFIX.sub("", k)
            tot[k] = tot.get(k, 0.0) + (t - s)
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in best]

    # -- programs and kernels ------------------------------------------------
    def modules(self, program: str):
        """Complete executions, inside the window, of the jitted program
        whose module is named ``program`` (``jit_<function>``; the trace
        adds a fingerprint in parentheses), on all chips."""
        return [e for p in self.devices for e in self.inside(p.modules)
                if e.name.split("(")[0] == program]

    def kernels(self, label: str):
        """Pallas kernel calls labelled ``label`` inside the window. A
        kernel's HLO instruction is named after the jitted wrapper that
        calls it (``_binary_matmul``, ``_xnor_matmul_packed``,
        ``sign_and_pack_patches``)."""
        return [e for p in self.devices for e in self.inside(p.ops)
                if is_kernel(e.name, label)]

    def host_spans(self, name: str):
        return [e for e in self.inside(self.host) if e.name == name]

    def calls(self, span: str, program: str):
        """[(host span, device execution)] of ``program``: each execution
        inside the window with the latest ``span`` that opened on the host
        before the program was enqueued (linked through the trace's flow
        ids; the enqueue itself may run on another host thread, just after
        the span closed)."""
        import bisect

        spans = sorted((e for e in self.host if e.name == span),
                       key=lambda e: e.start)
        starts = [e.start for e in spans]
        out = []
        for x in self.modules(program):
            t = self.enqueued.get(x.stats.get("_c"))
            if t is None:
                continue
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0:
                out.append((spans[i], x))
        return out
