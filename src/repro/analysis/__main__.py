"""CLI for the static verifier: ``python -m repro.analysis``.

Modes (combinable; findings are merged, the exit code is the gate):

* ``--plan manifest.json`` — plan lints over one or more manifests.
* ``--all-goldens`` — plan lints over every checked-in golden manifest
  in ``benchmarks/golden_plans/`` (the CI gate; non-plan JSON like the
  collective audit golden is skipped).
* ``--live MODE`` (repeatable: det / xnor) — full live-engine check in
  a forced-4-device subprocess: compiles the starcoder2-3b smoke plan
  on the 2x2 ("data", "model") mesh, runs plan lints against the real
  mesh, compiled-HLO lints (donation, upcasts, host transfers) with the
  committed collective budget from ``collectives.json``, then a short
  ``stream_serve`` with mid-stream refill under the retrace sentinel.

``--json out.json`` writes the merged findings machine-readably;
``--waive RULE`` drops a rule id before gating. Exit code 0 iff no
error-severity finding survives.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys
from typing import Dict, List, Optional

from repro.analysis.findings import (Finding, findings_to_json,
                                     format_findings, gate, waive)
from repro.analysis.plan_lints import lint_plan_file

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                     os.pardir, os.pardir, os.pardir))
_GOLDEN_DIR = os.path.join(_REPO, "benchmarks", "golden_plans")
_COLLECTIVES_GOLDEN = os.path.join(_GOLDEN_DIR, "collectives.json")

# live-smoke geometry — mirrors benchmarks/check_collectives.py, so the
# committed collective budget applies verbatim
_ARCH = "starcoder2_3b"
_MESH_SHAPE = (2, 2)
_MESH_AXES = ("data", "model")
_SLOTS = 4
_PROMPT_LEN = 8
_MAX_NEW_CAP = 8


def _parse_axis_sizes(arg: Optional[str]) -> Optional[Dict[str, int]]:
    if not arg:
        return None
    out = {}
    for item in arg.split(","):
        name, _, size = item.partition("=")
        out[name.strip()] = int(size)
    return out


def _lint_manifest(path: str, mesh_axes: Optional[List[str]],
                   axis_sizes: Optional[Dict[str, int]]) -> List[Finding]:
    _, findings = lint_plan_file(path, mesh_axes=mesh_axes,
                                 axis_sizes=axis_sizes)
    return findings


def _golden_plan_files() -> List[str]:
    files = []
    for path in sorted(glob.glob(os.path.join(_GOLDEN_DIR, "*.json"))):
        with open(path) as f:
            if "layers" in json.load(f):
                files.append(path)
    return files


# ---------------------------------------------------------------------------
# live-engine smoke (runs inside the forced-multi-device subprocess)
# ---------------------------------------------------------------------------

def _live_child(mode: str) -> List[Finding]:
    import jax
    import numpy as np

    from repro.analysis.hlo_lints import lint_engine
    from repro.analysis.retrace import RetraceSentinel
    from repro.configs import base as cb
    from repro.core.policy import DEFAULT_POLICY
    from repro.distributed.sharding import make_mesh
    from repro.engine import compile_plan
    from repro.models import transformer as T
    from repro.serve.batcher import SlotBatcher
    from repro.serve.engine import ServeEngine, stream_serve

    mesh = make_mesh(_MESH_SHAPE, _MESH_AXES)
    axis_sizes = dict(zip(_MESH_AXES, _MESH_SHAPE))
    cfg = cb.get_config(_ARCH, smoke=True)
    params = T.init_lm(cfg, jax.random.key(0))
    plan = compile_plan(params, DEFAULT_POLICY, mode, warn=False, mesh=mesh)

    findings = plan.lint(mesh_axes=mesh.axis_names, axis_sizes=axis_sizes)

    packed = plan.pack(params, key=jax.random.key(1))
    engine = ServeEngine(cfg, packed, mesh=mesh, plan=plan)

    budgets = None
    if os.path.exists(_COLLECTIVES_GOLDEN):
        with open(_COLLECTIVES_GOLDEN) as f:
            audits = json.load(f)["audits"].get(mode, {})
        budgets = {entry: a["counts"] for entry, a in audits.items()}
    findings += lint_engine(engine, n_slots=_SLOTS, prompt_len=_PROMPT_LEN,
                            max_new_cap=_MAX_NEW_CAP, budgets=budgets)

    # serving smoke: more requests than slots forces mid-stream refill;
    # staggered max_new forces slot turnover — zero post-warmup recompiles
    sentinel = RetraceSentinel(engine)
    batcher = SlotBatcher(_SLOTS, _PROMPT_LEN)
    for i in range(_SLOTS + 2):
        prompt = np.full((_PROMPT_LEN,), 1 + i, dtype=np.int32)
        batcher.submit(prompt, max_new=3 + (i % 3))
    steps = stream_serve(engine, batcher, max_new_cap=_MAX_NEW_CAP,
                         sentinel=sentinel)
    print(f"live[{mode}]: {steps} steps; {sentinel.summary()}",
          file=sys.stderr)
    findings += sentinel.findings()
    return findings


def _run_live(mode: str, timeout: int = 540) -> Optional[List[Finding]]:
    """Forced-4-device subprocess wrapper (device count is fixed at
    backend init, so the live check cannot run in-process)."""
    code = (f"from repro.analysis.__main__ import _live_child; "
            f"from repro.analysis.findings import findings_to_json; "
            f"import json; "
            f"print('FINDINGS ' + json.dumps(findings_to_json("
            f"_live_child({mode!r}))))")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_REPO, "src"), env.get("PYTHONPATH", "")])
    try:
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True,
                              timeout=timeout)
    except (OSError, subprocess.TimeoutExpired):
        return None
    sys.stderr.write(proc.stderr[-2000:] if proc.returncode else
                     "".join(line + "\n"
                             for line in proc.stderr.splitlines()
                             if line.startswith("live[")))
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("FINDINGS "):
            return [Finding.from_json(d)
                    for d in json.loads(line[len("FINDINGS "):])]
    return None


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.analysis",
                                 description=__doc__)
    ap.add_argument("--plan", action="append", default=[],
                    metavar="MANIFEST", help="lint a plan manifest")
    ap.add_argument("--all-goldens", action="store_true",
                    help="lint every golden manifest in "
                         "benchmarks/golden_plans/")
    ap.add_argument("--live", action="append", default=[],
                    choices=("det", "stoch", "xnor"),
                    help="live-engine check for a mode (forced 4-device "
                         "subprocess; repeatable)")
    ap.add_argument("--mesh-axes", default=None,
                    help="comma-separated axis vocabulary for plan lints "
                         "(default: data,model,pod)")
    ap.add_argument("--axis-sizes", default=None,
                    help="axis sizes for plan lints, e.g. model=2,data=2")
    ap.add_argument("--waive", action="append", default=[], metavar="RULE",
                    help="drop a rule id before gating (repeatable)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write merged findings as JSON")
    args = ap.parse_args(argv)

    mesh_axes = args.mesh_axes.split(",") if args.mesh_axes else None
    axis_sizes = _parse_axis_sizes(args.axis_sizes)

    plans = list(args.plan)
    if args.all_goldens:
        plans += _golden_plan_files()
    if not plans and not args.live:
        ap.error("nothing to do: pass --plan, --all-goldens, or --live")

    findings: List[Finding] = []
    for path in plans:
        batch = _lint_manifest(path, mesh_axes, axis_sizes)
        findings += batch
        rel = os.path.relpath(path, _REPO)
        print(format_findings(batch, title=f"plan lints: {rel}"))
    for mode in args.live:
        batch = _run_live(mode)
        if batch is None:
            print(f"live[{mode}]: subprocess unavailable, skipping "
                  f"(no multi-device CPU mesh)", file=sys.stderr)
            continue
        findings += batch
        print(format_findings(batch, title=f"live engine: {mode}"))

    findings = waive(findings, args.waive)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(findings_to_json(findings), f, indent=1)
            f.write("\n")
    code = gate(findings)
    print(f"repro.analysis: {'FAIL' if code else 'OK'} "
          f"({len(findings)} finding(s) after waivers)")
    return code


if __name__ == "__main__":
    sys.exit(main())
