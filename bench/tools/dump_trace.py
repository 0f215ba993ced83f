"""Traces a few seconds of one cell and prints the trace's structure.

  python3 bench/tools/dump_trace.py --workload <cell> --seed <n> \\
      --seconds <traced s> --out <dir>

Keeps the ``.xplane.pb`` under ``--out`` and prints its planes and lines,
the device programs and the op names with their stats, and the host spans:
what to read before writing a reduction against the trace.
"""
import argparse
import collections
import glob
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))]
sys.path.insert(1, os.path.join(sys.path[0], "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness  # noqa: E402


def describe(path: str) -> None:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    print(f"{path}: {os.path.getsize(path)} bytes")
    for plane in pd.planes:
        for line in plane.lines:
            evs = list(line.events)
            print(f"PLANE {plane.name!r} LINE {line.name!r}: {len(evs)} events")
            names = collections.Counter(e.name for e in evs)
            seen = set()
            for e in evs:
                if e.name in seen or len(seen) >= 25:
                    continue
                seen.add(e.name)
                st = {k: (v[:300] if isinstance(v, str) else v)
                      for k, v in dict(e.stats).items()}
                print(f"   {e.name[:160]!r} x{names[e.name]} "
                      f"dur {e.duration_ns:.0f} ns stats {st}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    from bench.trace.tracer import TraceWindow

    cell = harness.load_cell(a.workload)
    harness.prepare(cell)
    cell.traffic["trace_seconds"] = a.seconds
    TraceWindow.keep_dir = a.out
    sysmod = harness.load_module(f"bench/systems/{cell.config['system']}.py")
    system = sysmod.System(cell.config, a.seed, True)
    system.warm_up(cell.traffic)
    try:
        win = system.window(cell.traffic, a.seconds + 1.0, True)
        tr = win.trace
        print(f"window {tr.window_s:.6f} s, busy {tr.busy_s():.6f} s")
        print("top ops", tr.top_ops(15))
        print("idle by host span", tr.idle_by_host_span(15))
        print("modules", collections.Counter(
            e.name.split("(")[0] for p in tr.devices for e in p.modules))
    finally:
        for p in glob.glob(os.path.join(a.out, "*.xplane.pb")):
            describe(p)


if __name__ == "__main__":
    main()
