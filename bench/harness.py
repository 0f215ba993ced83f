"""One run of one cell: find its pieces by name, set up, measure, check.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
harness reads ``bench/configs/<config>.json`` and ``bench/traffic/<cell>.json``,
builds the configuration through ``bench/systems/<system>.py``, warms up the
cell's shapes, measures for ``--seconds``, and then compares what the timed
path produced with the configuration's plain reference. Every file is looked
up first under the benchmark's root and then beside this module, so a cell,
a configuration or a per-layer metric is added with new files and new
entries in ``BENCHMARK.json`` alone.

The last line on stdout is the result, as one JSON object. The compared
numbers, each beside its limit, are also the last lines on stderr.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
import traceback
from typing import Optional

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
_roots = [CHECKOUT]
_modules: dict = {}


class BenchError(Exception):
    """A run that cannot produce a result (no chip, unknown cell, ...)."""


def set_root(root: str) -> None:
    """Look files up under ``root`` first (then beside this package)."""
    _roots[:] = [os.path.abspath(root)]
    if os.path.abspath(root) != CHECKOUT:
        _roots.append(CHECKOUT)


def find(relpath: str) -> str:
    for r in _roots:
        p = os.path.join(r, relpath)
        if os.path.exists(p):
            return p
    raise BenchError(f"no {relpath} under {' or '.join(_roots)}")


def load_json(relpath: str) -> dict:
    with open(find(relpath)) as f:
        return json.load(f)


def load_module(relpath: str):
    """Imports ``relpath`` (a file under a root) once, by path; names with
    dots, such as a metric's, are fine."""
    path = find(relpath)
    if path not in _modules:
        name = "bench._by_name." + relpath.replace("/", "__").replace(".", "_")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        _modules[path] = mod
    return _modules[path]


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list       # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def reports(metric: dict, cell: str, moved: Optional[set] = None) -> bool:
    """Whether ``cell`` reports ``metric``: listed in its ``workloads``, or,
    without that key, every cell (per-layer: every cell reporting the
    end-to-end metric it moves)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return moved is None or metric["moves"] in moved


def load_cell(name: str) -> Cell:
    bench = load_json("BENCHMARK.json")
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    with open(find(cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = load_json(f"bench/traffic/{name}.json")
    e2e = [m for m in bench["end_to_end"] if reports(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if reports(m, name, moved)]
    return Cell(name, wl, config, traffic, e2e, per_layer)


class CompileClock:
    """Backend compilations in this process, with the host time of each
    (``jax.monitoring``; a program loaded from the persistent cache does not
    count)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.events: list[tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.events.append((time.perf_counter(), duration))

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.events if t0 <= t <= t1)


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric reader gets: the cell, the configuration's
    ``model``, the traffic, the chip's peaks, the window's host record and
    the reduced trace (``bench.trace.reduce.Trace``)."""

    cell: str
    model: dict
    traffic: dict
    peaks: dict
    window: object
    trace: object


def devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs


def prepare(cell: Cell, require_tpu: bool = True):
    """Finds the cell's chips and turns on the persistent compile cache in
    the checkout (``repro.launch.compile_cache``); returns the devices."""
    devs = devices(int(cell.workload["chips"]), require_tpu)
    if require_tpu:
        import jax

        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return devs


def memory_peak(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(argv, t_start: float, *, root: Optional[str] = None,
        require_tpu: bool = True, peaks: Optional[dict] = None,
        fault=None) -> dict:
    """One run; returns the result object. ``fault(system)``, for tests,
    breaks the timed path after set-up."""
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    prev = list(_roots)
    if root is not None:
        set_root(root)
    try:
        return _run(args, t_start, require_tpu, peaks, fault)
    finally:
        _roots[:] = prev


def _run(args, t_start, require_tpu, peaks, fault) -> dict:
    cell = load_cell(args.workload)
    traced = bool(args.trace)

    devs = prepare(cell, require_tpu)
    kind = devs[0].device_kind
    if peaks is None:
        from bench.work.roofline import load_peaks

        peaks = load_peaks(kind)
    clock = CompileClock()

    sysmod = load_module(f"bench/systems/{cell.config['system']}.py")
    system = sysmod.System(cell.config, args.seed, traced)
    system.warm_up(cell.traffic)
    if fault is not None:
        fault(system)
    win = system.window(cell.traffic, args.seconds, traced)
    setup_s = win.t_open - t_start
    compiles = clock.between(win.t_open, win.t_close)
    mem = memory_peak(devs)
    e2e, attempted, failed = system.end_to_end(win, cell.traffic)

    log = []
    log.append(f"compilations inside the window: {compiles}")
    if getattr(win, "lateness", None):
        from bench.stats import percentile

        log.append(f"generator lateness: p95 "
                   f"{percentile(win.lateness, 95) * 1e3:.3f} ms, max "
                   f"{max(win.lateness) * 1e3:.3f} ms over "
                   f"{len(win.lateness)} submits")
    metrics, device = {}, {"platform": devs[0].platform, "kind": kind,
                           "count": len(devs), "memory_peak_bytes": mem}
    breakdown = None
    if traced:
        tr = win.trace
        ctx = MetricContext(cell.name, cell.config["model"], cell.traffic,
                            peaks, win, tr)
        for m in cell.per_layer:
            reader = load_module(f"bench/metrics/{m['name']}.py")
            got = reader.read(ctx)
            if got is None:
                continue
            value, note = got if isinstance(got, tuple) else (got, None)
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            if note:
                log.append(f"{m['name']}: {value:.6g} {m['unit']} ({note})")
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": tr.idle_by_host_span(10)}
    else:
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": "s"}
            elif e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    for line in log:
        print(line, file=sys.stderr)

    system.release()
    gc.collect()
    readings = system.check(win, cell.traffic)
    limits = cell.traffic["check"]["limits"]
    checks = {k: {"value": _finite(readings.get(k)), "limit": lim}
              for k, lim in limits.items()}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def _finite(v):
    """A reading as JSON can hold it (None for a missing or infinite one)."""
    import math

    return float(v) if v is not None and math.isfinite(v) else None


#: a run still going after this is stopped (a first run, which compiles,
#: may take up to 1200 s; a warm one ends within 360 s)
RUN_LIMIT_S = 1150.0


def watchdog(seconds: float) -> None:
    """Ends the process (exit code 3, no result) if it is still running after
    ``seconds``: a hung device wait must not hold the chip."""
    import threading

    def stop():
        print(f"bench: still running after {seconds:.0f} s; stopped",
              file=sys.stderr, flush=True)
        os._exit(3)

    t = threading.Timer(seconds, stop)
    t.daemon = True
    t.start()


def main(argv, t_start: float) -> int:
    watchdog(RUN_LIMIT_S - (time.perf_counter() - t_start))
    try:
        result = run(argv, t_start)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    sys.stdout.flush()
    print(json.dumps(result))
    return 0
