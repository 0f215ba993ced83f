"""Readings for the limits of ``correct``: the program's compared number and
the control's on many seeds, in one process.

  python3 bench/tools/calibrate.py --workload <cell> --seeds 1,2,3 \\
      --seconds 20 --control int8,fp8

For each seed it builds the cell's configuration, runs a window of the
cell's own traffic and geometry, frees the program's state and compares, as
a run does, the timed path's output with the reference; then it computes
the same comparison for the reference put in the program's place in each
lower ``--control`` precision. One JSON line per seed.
"""
import argparse
import gc
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))]
sys.path.insert(1, os.path.join(sys.path[0], "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness  # noqa: E402


def readings(cell, seed: int, seconds: float, controls) -> dict:
    sysmod = harness.load_module(f"bench/systems/{cell.config['system']}.py")
    system = sysmod.System(cell.config, seed, False)
    system.warm_up(cell.traffic)
    win = system.window(cell.traffic, seconds, False)
    system.release()
    gc.collect()
    out = {"seed": seed, **system.check(win, cell.traffic)}
    for q in controls:
        got = system.check(win, cell.traffic, quant=q)
        out.update({f"{k}.{q}": v for k, v in got.items()
                    if k.startswith("control")})
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", default="int8")
    a = ap.parse_args()
    cell = harness.load_cell(a.workload)
    harness.prepare(cell)
    controls = [c for c in a.control.split(",") if c]
    for s in a.seeds.split(","):
        print(json.dumps(readings(cell, int(s), a.seconds, controls)),
              flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
