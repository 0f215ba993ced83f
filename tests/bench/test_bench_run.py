"""Whole runs of the harness on the CPU, with the look for a chip skipped:
a cell added with new files only, and the timed path broken underneath so
that ``correct`` comes out false."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness

from bench_helpers import CPU_PEAKS, REPO, write_smoke_root

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "chat_decode.xplane.pb")


def run_cell(root, cell, seed=3, fault=None):
    return harness.run(["--workload", cell, "--seed", str(seed),
                        "--seconds", "1", "--trace", "0"],
                       time.perf_counter(), root=root, require_tpu=False,
                       peaks=CPU_PEAKS, fault=fault)


def test_run_py_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    args = ["--workload", "starcoder2_3b-det.chat", "--seed", "1",
            "--seconds", "1", "--trace", "0"]
    p = subprocess.run([sys.executable, "bench/run.py", *args], cwd=REPO,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr
    # a directory with the benchmark's own files only
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    p = subprocess.run([sys.executable, "bench/run.py", *args],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""


def test_a_cell_config_and_metric_added_with_new_files_only(tmp_path):
    """The harness finds a throwaway configuration, traffic mix and
    per-layer metric by name under a new root; no existing file changes."""
    root = write_smoke_root(str(tmp_path))
    os.makedirs(tmp_path / "bench" / "metrics")
    with open(tmp_path / "bench/configs/lm_smoke.json") as f:
        cfg = json.load(f)
    cfg["model"]["d_ff"] = 256
    (tmp_path / "bench/configs/lm_tiny.json").write_text(json.dumps(cfg))
    with open(tmp_path / "bench/traffic/lm_smoke.chat.json") as f:
        tr = json.load(f)
    (tmp_path / "bench/traffic/lm_tiny.burst.json").write_text(
        json.dumps(dict(tr, rate_per_s=60.0)))
    (tmp_path / "bench/metrics/decode_calls.py").write_text(
        "def read(ctx):\n"
        "    return float(len(ctx.trace.modules('jit__decode_fn')))\n")
    with open(tmp_path / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["configs"].append({"name": "lm_tiny", "source": "x", "why": "x",
                             "file": "bench/configs/lm_tiny.json",
                             "reduced": []})
    bench["workloads"].append({"name": "lm_tiny.burst", "config": "lm_tiny",
                               "traffic": "burst", "chips": 1, "why": "x"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p50_ms", "itl_p95_ms"):
            m["workloads"].append("lm_tiny.burst")
    bench["per_layer"].append({
        "name": "decode_calls", "unit": "calls", "better": "higher",
        "source": "device_trace", "layer": "model step",
        "moves": "itl_p95_ms", "workloads": ["lm_tiny.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    res = run_cell(root, "lm_tiny.burst")
    assert res["correct"] is True
    assert set(res["metrics"]) == {"ttft_p50_ms", "itl_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    harness.set_root(root)
    try:
        cell = harness.load_cell("lm_tiny.burst")
        assert [m["name"] for m in cell.per_layer][-1] == "decode_calls"
        reader = harness.load_module("bench/metrics/decode_calls.py")
        assert reader.__file__.startswith(str(tmp_path))
        from bench.trace.reduce import Trace

        ctx = harness.MetricContext(cell.name, cell.config["model"],
                                    cell.traffic, CPU_PEAKS, None,
                                    Trace(FIXTURE))
        assert reader.read(ctx) == 17.0
    finally:
        harness.set_root(harness.CHECKOUT)


def test_a_token_altered_where_it_is_produced_fails(smoke_root):
    import dataclasses

    import jax.numpy as jnp

    def fault(system):
        eng = system.engine
        for name in ("decode_step", "fused_step", "prefill_chunk_into"):
            orig = getattr(eng, name)

            def altered(*a, _orig=orig, **k):
                st = _orig(*a, **k)
                # every slot's next token becomes its runner-up's neighbour
                return dataclasses.replace(
                    st, logits=jnp.roll(st.logits, 1, axis=-1))

            setattr(eng, name, altered)

    res = run_cell(smoke_root, "lm_smoke.chat", fault=fault)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > res["checks"][
        "logit_gap"]["limit"]


def test_an_answer_altered_where_it_is_produced_fails(smoke_root):
    import jax.numpy as jnp

    def fault(system):
        fwd = system.fwd
        system.fwd = lambda p, s, x: jnp.roll(fwd(p, s, x), 1, axis=-1)

    res = run_cell(smoke_root, "vgg_smoke.b4", fault=fault)
    assert res["correct"] is False
    assert res["checks"]["top1_mismatch"]["value"] > 0.5


@pytest.mark.parametrize("cell", ["lm_smoke.chat", "vgg_smoke.b4"])
def test_sound_runs_are_correct(smoke_root, cell):
    res = run_cell(smoke_root, cell, seed=2**31 + 5)
    assert res["correct"] is True
    assert res["failed"] <= res["attempted"]
    assert res["device"]["count"] == 1
