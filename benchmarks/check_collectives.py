"""Golden per-step collective audit: measured vs committed, fail loudly.

The ROADMAP's sharded-serving hunt needs its success metric pinned: the
*exact* number (and operand bytes) of collectives one ``decode_step`` /
``prefill_into`` executes for the det and xnor sharded golden plans on the
2x2 ("data", "model") mesh. A code change that silently adds an all-gather
to the decode step — a plan sharding tweak, a cache layout change, a new
engine epilogue — shifts serving throughput without failing any numeric
test. This gate compiles the actual jitted serving programs on a forced
4-device CPU mesh (in a subprocess: device count is fixed at backend init),
audits their SPMD HLO via ``repro.obs.collectives``, and diffs against the
manifest committed in ``benchmarks/golden_plans/collectives.json``.

  PYTHONPATH=src python -m benchmarks.check_collectives          # check
  PYTHONPATH=src python -m benchmarks.check_collectives --write  # regen

Regenerate (and commit) the golden only when a collective change is
intentional; the printed diff is the review artifact. Counts are exact
integers; bytes are exact operand sums — but both can legitimately move
under an XLA upgrade (the partitioner chooses the collectives), so a
version bump that shifts them is also a --write-and-review event.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

GOLDEN = os.path.join(os.path.dirname(__file__), "golden_plans",
                      "collectives.json")

# audit geometry — mirrors serve_bench's sharded row: starcoder2-3b smoke
# config, 2x2 ("data", "model") mesh, 4 slots (even data-axis split)
ARCH = "starcoder2_3b"
MODES = ("det", "xnor")
MESH_SHAPE = (2, 2)
MESH_AXES = ("data", "model")
SLOTS = 4
PROMPT_LEN = 8
MAX_NEW_CAP = 8


def _child() -> dict:
    """Runs inside the forced-multi-device subprocess: builds the sharded
    engine per mode and audits its compiled decode/prefill programs."""
    import jax

    from repro.configs import base as cb
    from repro.core.policy import DEFAULT_POLICY
    from repro.distributed.sharding import make_mesh
    from repro.engine import compile_plan
    from repro.models import transformer as T
    from repro.obs.collectives import audit_engine
    from repro.serve.engine import ServeEngine

    mesh = make_mesh(MESH_SHAPE, MESH_AXES)
    cfg = cb.get_config(ARCH, smoke=True)
    params = T.init_lm(cfg, jax.random.key(0))
    out = {}
    for mode in MODES:
        plan = compile_plan(params, DEFAULT_POLICY, mode, warn=False,
                            mesh=mesh)
        packed = plan.pack(params, key=jax.random.key(1))
        engine = ServeEngine(cfg, packed, mesh=mesh, plan=plan)
        audits = audit_engine(engine, n_slots=SLOTS, prompt_len=PROMPT_LEN,
                              max_new_cap=MAX_NEW_CAP)
        out[mode] = {name: a.to_json() for name, a in audits.items()}
    return out


def measured(timeout: int = 540) -> dict | None:
    """Measured audit dict, or None if the subprocess cannot run."""
    code = ("import benchmarks.check_collectives as cc, json; "
            "print('RESULT ' + json.dumps(cc._child()))")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), os.pardir, "src"),
         os.path.join(os.path.dirname(__file__), os.pardir),
         env.get("PYTHONPATH", "")])
    try:
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=timeout)
    except (OSError, subprocess.TimeoutExpired):
        return None
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    print(f"collective-audit child failed:\n{proc.stderr[-2000:]}",
          file=sys.stderr)
    return None


# canonical category order for the mismatch table; anything else the audit
# ever reports (e.g. a new collective kind from an XLA upgrade) is appended
CATEGORIES = ("all-gather", "all-reduce", "all-to-all", "collective-permute")


def _entry_table(mode: str, entry: str, w: dict, g: dict) -> list[str]:
    """Per-category delta table for one drifted program: golden vs measured
    count + operand bytes per collective kind, plus reshard copies and the
    totals — the whole decode-step budget at a glance."""
    kinds = list(CATEGORIES) + sorted(
        (set(w.get("counts", {})) | set(g.get("counts", {})))
        - set(CATEGORIES))

    def row(name, wc, wb, gc, gb):
        flag = "   " if (wc, wb) == (gc, gb) else " <-"
        return (f"    {name:<20} {wc:>6} {wb:>12,.0f}   "
                f"{gc:>6} {gb:>12,.0f}{flag}")

    lines = [f"  {mode}/{entry}:",
             f"    {'category':<20} {'golden':>6} {'bytes':>12}   "
             f"{'measured':>6} {'bytes':>12}"]
    for k in kinds:
        lines.append(row(k, w.get("counts", {}).get(k, 0),
                         w.get("bytes", {}).get(k, 0.0),
                         g.get("counts", {}).get(k, 0),
                         g.get("bytes", {}).get(k, 0.0)))
    lines.append(row("reshard-copies",
                     w.get("reshard_copies", 0),
                     w.get("reshard_copy_bytes", 0.0),
                     g.get("reshard_copies", 0),
                     g.get("reshard_copy_bytes", 0.0)))
    lines.append(row("total collectives",
                     sum(w.get("counts", {}).values()),
                     sum(w.get("bytes", {}).values()),
                     sum(g.get("counts", {}).values()),
                     sum(g.get("bytes", {}).values())))
    return lines


def _diff(want: dict, got: dict) -> list[str]:
    lines = []
    for mode in sorted(set(want) | set(got)):
        w_mode, g_mode = want.get(mode, {}), got.get(mode, {})
        for entry in sorted(set(w_mode) | set(g_mode)):
            w, g = w_mode.get(entry), g_mode.get(entry)
            if w == g:
                continue
            if w is None or g is None:
                lines.append(f"  {mode}/{entry}: "
                             f"{'NEW' if w is None else 'MISSING'}")
                continue
            lines.extend(_entry_table(mode, entry, w, g))
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--write", action="store_true",
                    help="(re)write the golden audit instead of checking")
    args = ap.parse_args(argv)

    got = measured()
    if got is None:
        print("collective audit: subprocess unavailable, skipping "
              "(no multi-device CPU mesh)", file=sys.stderr)
        return 0

    if args.write:
        payload = {"arch": ARCH, "smoke": True,
                   "mesh": {"shape": list(MESH_SHAPE),
                            "axes": list(MESH_AXES)},
                   "geometry": {"n_slots": SLOTS, "prompt_len": PROMPT_LEN,
                                "max_new_cap": MAX_NEW_CAP},
                   "audits": got}
        with open(GOLDEN, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {GOLDEN}")
        return 0

    if not os.path.exists(GOLDEN):
        print(f"missing golden {GOLDEN}; run with --write", file=sys.stderr)
        return 1
    with open(GOLDEN) as f:
        want = json.load(f)["audits"]
    lines = _diff(want, got)
    if lines:
        print("per-step collective audit drifted from golden "
              "(review, then --write if intentional):")
        print("\n".join(lines))
        return 1
    n = {m: sum(got[m]["decode_step"]["counts"].values()) for m in got}
    print("collective audit matches golden: " + ", ".join(
        f"{m}: {c} collectives/decode_step" for m, c in sorted(n.items())))
    return 0


if __name__ == "__main__":
    sys.exit(main())
