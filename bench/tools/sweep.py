"""Finds the knee of an open-loop LM cell: one process, one set-up, one
window per offered rate.

  python3 bench/tools/sweep.py --workload <cell> --rates 2,3,4 \\
      --seconds 20 --lead 4

For each rate it prints one JSON line: requests due in the window, the share
of them that got their first token inside it, the requests waiting for a
slot at each quarter of the window (a queue that grows over the window
means the rate is past the knee), TTFT and inter-token p50/p95, and tokens
per second. The knee is the highest rate at which the queue does not grow
and at least 95% of the requests due get their first token in the window.
"""
import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))]
sys.path.insert(1, os.path.join(sys.path[0], "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness, stats  # noqa: E402


def waiting(stamps, t):
    return sum(1 for s in stamps
               if s.due <= t and (s.refill is None or s.refill > t))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--lead", type=float, default=4.0)
    a = ap.parse_args()
    cell = harness.load_cell(a.workload)
    harness.prepare(cell)
    sysmod = harness.load_module(f"bench/systems/{cell.config['system']}.py")
    system = sysmod.System(cell.config, a.seed, False)
    system.warm_up(cell.traffic)
    for r in [float(x) for x in a.rates.split(",")]:
        tr = dict(cell.traffic, rate_per_s=r, lead_in_s=a.lead)
        w = system.window(tr, a.seconds, False)
        t0, t1 = w.t_open, w.t_close
        ttft, due, failed = stats.ttft_samples(w.stamps, t0, t1)
        itl = stats.itl_samples(w.stamps, t0, t1)
        print(json.dumps({
            "rate_per_s": r, "due": due,
            "first_token_share": (due - failed) / max(due, 1),
            "waiting": [waiting(w.stamps, t0 + k * (t1 - t0) / 4)
                        for k in range(5)],
            "ttft_p50_ms": stats.percentile(ttft, 50) * 1e3,
            "ttft_p95_ms": stats.percentile(ttft, 95) * 1e3,
            "itl_p50_ms": stats.percentile(itl, 50) * 1e3,
            "itl_p95_ms": stats.percentile(itl, 95) * 1e3,
            "tokens_per_s": stats.tokens_in(w.stamps, t0, t1) / (t1 - t0),
        }), flush=True)


if __name__ == "__main__":
    main()
