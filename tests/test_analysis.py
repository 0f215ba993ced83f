"""Static verifier (repro.analysis): clean passes on the shipped golden
manifests, a red test per lint rule (deliberately broken plan / HLO /
engine, rule id asserted), the retrace sentinel unit + live behavior, and
the CLI gate.

Multi-device pieces run in subprocesses with forced host devices
(mirroring tests/test_obs_collectives.py)."""
import copy
import glob
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import (ERROR, INFO, Finding, RetraceError,
                            RetraceSentinel, errors, findings_to_json,
                            format_findings, gate, lint_cache_donation,
                            lint_collective_budget, lint_f32_upcast,
                            lint_hlo, lint_host_transfer, lint_plan, waive)
from repro.engine import ExecutionPlan

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), os.pardir,
                          "benchmarks", "golden_plans")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def golden_plan_files():
    out = []
    for path in sorted(glob.glob(os.path.join(GOLDEN_DIR, "*.json"))):
        with open(path) as f:
            if "layers" in json.load(f):
                out.append(path)
    return out


def rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# findings plumbing
# ---------------------------------------------------------------------------

class TestFindings:
    def test_round_trip_and_gate(self):
        f = Finding(rule="plan.dense_fallthrough", severity=ERROR,
                    where="fc/0/kernel", message="m", hint="h",
                    data={"k": 30})
        g = Finding.from_json(json.loads(json.dumps(f.to_json())))
        assert g == f
        info = Finding(rule="plan.boundary_reshard", severity=INFO,
                       where="x", message="m")
        assert gate([f, info]) == 1 and gate([info]) == 0
        assert errors([f, info]) == [f]

    def test_waive_drops_by_rule_id(self):
        f = Finding(rule="hlo.f32_upcast", severity=ERROR, where="e",
                    message="m")
        assert waive([f], ["hlo.f32_upcast"]) == []
        assert waive([f], ["other.rule"]) == [f]
        assert gate(waive([f], ["hlo.f32_upcast"])) == 0

    def test_invalid_severity_rejected(self):
        with pytest.raises(ValueError, match="severity"):
            Finding(rule="r", severity="fatal", where="w", message="m")

    def test_format_orders_errors_first(self):
        out = format_findings([
            Finding(rule="b.info", severity=INFO, where="w", message="m"),
            Finding(rule="a.err", severity=ERROR, where="w", message="m",
                    hint="do the thing"),
        ], title="t")
        assert out.index("a.err") < out.index("b.info")
        assert "fix: do the thing" in out
        assert "1 error(s)" in out
        assert "no findings" in format_findings([], title="t")


# ---------------------------------------------------------------------------
# plan lints: clean pass on every shipped golden, red test per rule
# ---------------------------------------------------------------------------

class TestPlanLintsClean:
    @pytest.mark.parametrize("path", golden_plan_files(),
                             ids=lambda p: os.path.basename(p))
    def test_golden_manifests_have_no_errors(self, path):
        plan = ExecutionPlan.load(path)
        findings = lint_plan(plan)
        assert errors(findings) == [], findings_to_json(errors(findings))

    def test_boundary_reshard_is_informational_on_goldens(self):
        """The packed->dense boundary at the paper nets' final dense
        layer is real and expected: reported, but never gating."""
        plan = ExecutionPlan.load(
            os.path.join(GOLDEN_DIR, "mnist_fc_det.json"))
        findings = lint_plan(plan)
        hits = [f for f in findings if f.rule == "plan.boundary_reshard"]
        assert hits and all(f.severity == INFO for f in hits)
        assert gate(findings) == 0


class TestPlanLintsRed:
    @pytest.fixture()
    def det_plan(self):
        return ExecutionPlan.load(
            os.path.join(GOLDEN_DIR, "mnist_fc_det.json"))

    @pytest.fixture()
    def stoch_plan(self):
        return ExecutionPlan.load(
            os.path.join(GOLDEN_DIR, "mnist_fc_stoch.json"))

    def _packed_row(self, plan):
        rows = [a for a in plan.layers if a.backend == "packed"]
        assert rows
        return rows[0]

    def test_dense_fallthrough_fires(self, det_plan):
        plan = copy.deepcopy(det_plan)
        row = self._packed_row(plan)
        row.backend = "dense"
        row.reason = "cannot pack: K % 32 != 0 (K=30)"
        findings = lint_plan(plan)
        hits = [f for f in findings if f.rule == "plan.dense_fallthrough"]
        assert len(hits) == 1 and hits[0].severity == ERROR
        assert hits[0].where == row.path
        assert gate(findings) == 1

    def test_fallthrough_fires_from_a_real_compile(self):
        """End-to-end: a policy-selected K % 32 != 0 layer compiles to a
        dense fallthrough that the lint gates on."""
        import jax

        from repro.core.policy import DEFAULT_POLICY
        from repro.engine import compile_plan
        from repro.models import mnist_fc

        tree = mnist_fc.init(jax.random.key(0), hidden=(30, 64))
        plan = compile_plan(tree["params"], DEFAULT_POLICY, "det",
                            warn=False)
        hits = [f for f in lint_plan(plan)
                if f.rule == "plan.dense_fallthrough"]
        assert hits, "hidden=30 must fall through and be linted"

    def test_word_lane_split_fires_on_contraction_shard(self, det_plan):
        """'packed' declares no tp_contract_dim: model on the K dim is a
        word-lane / accumulation-order bug."""
        plan = copy.deepcopy(det_plan)
        row = self._packed_row(plan)
        row.sharding = ["model", None]
        hits = [f for f in lint_plan(plan)
                if f.rule == "plan.word_lane_split"]
        assert len(hits) == 1 and hits[0].where == row.path
        assert "accumulation order" in hits[0].message

    def test_word_lane_split_fires_on_uneven_word_split(self):
        """xnor may shard K (tp_contract_dim) — but only whole int32
        words per device."""
        plan = ExecutionPlan.load(
            os.path.join(GOLDEN_DIR, "mnist_fc_xnor.json"))
        plan = copy.deepcopy(plan)
        row = [a for a in plan.layers if a.backend == "xnor"][0]
        row.sharding = ["model", None]
        k = row.shape[-2]
        assert k % 32 == 0
        # k/32 words over 3 devices cannot split evenly
        uneven = {"model": 3} if (k // 32) % 3 else {"model": (k // 32) + 1}
        hits = [f for f in lint_plan(plan, axis_sizes=uneven)
                if f.rule == "plan.word_lane_split"]
        assert len(hits) == 1 and "whole" in hits[0].message
        # an even split of whole words is legal
        assert not [f for f in lint_plan(plan, axis_sizes={"model": 2})
                    if f.rule == "plan.word_lane_split"]

    def test_word_lane_split_fires_on_conv_folded_dims(self):
        plan = copy.deepcopy(ExecutionPlan.load(
            os.path.join(GOLDEN_DIR, "vgg16_cifar10_xnor.json")))
        row = [a for a in plan.layers if a.backend == "xnor_conv"][0]
        row.sharding = [None, None, "model", None]   # sharded C: folded
        hits = [f for f in lint_plan(plan)
                if f.rule == "plan.word_lane_split"]
        assert len(hits) == 1 and hits[0].where == row.path

    def test_unknown_axis_fires(self, det_plan):
        plan = copy.deepcopy(det_plan)
        row = self._packed_row(plan)
        row.sharding = [None, "modle"]               # typo
        hits = [f for f in lint_plan(plan) if f.rule == "plan.unknown_axis"]
        assert len(hits) == 1 and "modle" in hits[0].message
        # the same name is fine when the mesh really has it
        ok_axes = ("data", "model", "modle")
        assert not [f for f in lint_plan(plan, mesh_axes=ok_axes)
                    if f.rule == "plan.unknown_axis"]

    def test_unknown_replica_axis_fires(self, stoch_plan):
        plan = copy.deepcopy(stoch_plan)
        plan.replica_axis = "ensemble"
        hits = [f for f in lint_plan(plan) if f.rule == "plan.unknown_axis"]
        assert len(hits) == 1 and hits[0].where == "<replica_axis>"

    def test_replica_collision_fires(self, stoch_plan):
        """The stoch golden's packed rows shard 'model'; making 'model'
        the replica axis reuses one mesh axis on two tensor dims."""
        plan = copy.deepcopy(stoch_plan)
        plan.replica_axis = "model"
        hits = [f for f in lint_plan(plan)
                if f.rule == "plan.replica_axis_collision"]
        assert hits and all(h.severity == ERROR for h in hits)
        # 'data' does not collide (rows only use 'model')
        plan.replica_axis = "data"
        assert not [f for f in lint_plan(plan)
                    if f.rule == "plan.replica_axis_collision"]

    def test_plan_lint_method_hook(self, det_plan):
        assert det_plan.lint() == lint_plan(det_plan)


# ---------------------------------------------------------------------------
# HLO lints: synthetic red programs + real clean programs
# ---------------------------------------------------------------------------

_UPCAST_HLO = textwrap.dedent("""\
    HloModule m, entry_computation_layout={(bf16[512,512])->f32[512,512]}

    ENTRY %main (p0: bf16[512,512]) -> f32[512,512] {
      %p0 = bf16[512,512]{1,0} parameter(0)
      ROOT %convert.1 = f32[512,512]{1,0} convert(%p0), metadata={op_name="jit(f)/convert"}
    }
    """)

_HOST_HLO = textwrap.dedent("""\
    HloModule m

    ENTRY %main (p0: f32[64]) -> f32[64] {
      %p0 = f32[64]{0} parameter(0)
      %tok = token[] after-all()
      %snd = (f32[64], u32[], token[]) send(%p0, %tok), channel_id=1
      %sd = token[] send-done(%snd), channel_id=1
      ROOT %out = f32[64]{0} copy(%p0)
    }
    """)

_TWO_AR_HLO = textwrap.dedent("""\
    HloModule m

    %sum (a: f32[], b: f32[]) -> f32[] {
      %a = f32[] parameter(0)
      %b = f32[] parameter(1)
      ROOT %add = f32[] add(%a, %b)
    }

    ENTRY %main (p0: f32[8,16]) -> f32[8,16] {
      %p0 = f32[8,16]{1,0} parameter(0)
      %ar1 = f32[8,16]{1,0} all-reduce(%p0), to_apply=%sum, metadata={op_name="jit(f)/layer1/psum"}
      ROOT %ar2 = f32[8,16]{1,0} all-reduce(%ar1), to_apply=%sum, metadata={op_name="jit(f)/layer2/psum"}
    }
    """)


class TestHloLints:
    def test_f32_upcast_fires_and_respects_threshold(self):
        hits = lint_f32_upcast(_UPCAST_HLO, "decode_step", min_bytes=1024)
        assert len(hits) == 1 and hits[0].rule == "hlo.f32_upcast"
        assert hits[0].data["offenders"][0]["from"] == "bf16"
        assert "jit(f)/convert" in hits[0].message
        # 512*512*4 bytes < a huge threshold: below-threshold is clean
        assert lint_f32_upcast(_UPCAST_HLO, "d", min_bytes=10**9) == []

    def test_f32_upcast_clean_on_integer_converts(self):
        """s32->f32 converts (popcount/iota results) are not upcasts."""
        text = _UPCAST_HLO.replace("bf16", "s32")
        assert lint_f32_upcast(text, "d", min_bytes=1024) == []

    def test_cache_donation_red_and_clean(self):
        import jax
        import jax.numpy as jnp

        donated = jax.jit(lambda x: x * 2.0, donate_argnums=0).lower(
            jnp.ones((64, 64))).compile().as_text()
        assert lint_cache_donation(donated, "decode_step") == []
        undonated = jax.jit(lambda x: x * 2.0).lower(
            jnp.ones((64, 64))).compile().as_text()
        hits = lint_cache_donation(undonated, "decode_step")
        assert len(hits) == 1
        assert hits[0].rule == "hlo.cache_not_donated"
        assert hits[0].severity == ERROR

    def test_host_transfer_fires(self):
        hits = lint_host_transfer(_HOST_HLO, "decode_step")
        assert len(hits) == 1 and hits[0].rule == "hlo.host_transfer"
        assert "send" in hits[0].message

    def test_host_transfer_clean_on_device_only_program(self):
        import jax
        import jax.numpy as jnp

        text = jax.jit(lambda x: x @ x).lower(
            jnp.ones((16, 16))).compile().as_text()
        assert lint_host_transfer(text, "d") == []

    def test_collective_budget_blames_by_op_name(self):
        hits = lint_collective_budget(_TWO_AR_HLO, "decode_step",
                                      {"all-reduce": 1})
        assert len(hits) == 1 and hits[0].rule == "hlo.collective_budget"
        assert hits[0].data["over"]["all-reduce"] == {"measured": 2,
                                                      "budget": 1}
        blamed = {r["op_name"] for r in hits[0].data["blame"]}
        assert "jit(f)/layer1/psum" in blamed
        assert "jit(f)/layer2/psum" in blamed
        # within budget: clean
        assert lint_collective_budget(_TWO_AR_HLO, "d",
                                      {"all-reduce": 2}) == []

    def test_lint_hlo_composes(self):
        findings = lint_hlo(_TWO_AR_HLO, "decode_step",
                            budget={"all-reduce": 0},
                            require_donation=True)
        assert rules_of(findings) == {"hlo.collective_budget",
                                      "hlo.cache_not_donated"}


# ---------------------------------------------------------------------------
# retrace sentinel
# ---------------------------------------------------------------------------

class _FakeJit:
    def __init__(self):
        self.size = 0

    def _cache_size(self):
        return self.size


class TestRetraceSentinel:
    def test_warmup_compiles_are_free_then_growth_fires(self):
        decode, chunk = _FakeJit(), _FakeJit()
        s = RetraceSentinel(entries={"decode": decode,
                                     "decode_chunk": chunk},
                            warmup_steps=1)
        decode.size = 1          # first-step compile
        s.step()
        s.step()
        assert s.ok and s.steps == 2
        chunk.size = 2           # allowlisted: new chunk length
        s.step()
        assert s.ok
        decode.size = 2          # post-warmup retrace: the bug
        s.step()
        assert not s.ok and len(s.events) == 1
        e = s.events[0]
        assert e["entry"] == "decode" and e["step"] == 4
        f = s.findings()
        assert len(f) == 1 and f[0].rule == "serve.retrace"
        assert f[0].severity == ERROR
        assert "recompile" in s.summary()

    def test_strict_raises(self):
        decode = _FakeJit()
        s = RetraceSentinel(entries={"decode": decode}, warmup_steps=1,
                            strict=True)
        s.step()
        decode.size = 1
        with pytest.raises(RetraceError, match="decode"):
            s.step()

    def test_needs_engine_or_entries(self):
        with pytest.raises(ValueError):
            RetraceSentinel()

    def test_shape_change_is_caught_live(self):
        """The acceptance red test: serving again with a different prompt
        length recompiles prefill/decode, and the sentinel catches it."""
        import jax
        import numpy as np

        from repro.configs import base as cb
        from repro.models import transformer as T
        from repro.serve.batcher import SlotBatcher
        from repro.serve.engine import ServeEngine, stream_serve

        cfg = cb.get_config("starcoder2_3b", smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        engine = ServeEngine(cfg, params)
        sentinel = RetraceSentinel(engine, warmup_steps=1)

        def serve(prompt_len):
            b = SlotBatcher(2, prompt_len)
            for i in range(2):
                b.submit(np.full((prompt_len,), 1 + i, dtype=np.int32),
                         max_new=3)
            return stream_serve(engine, b, max_new_cap=4,
                                sentinel=sentinel)

        serve(prompt_len=8)
        assert sentinel.ok, sentinel.summary()   # steady state: no events
        serve(prompt_len=16)                     # shape change mid-session
        assert not sentinel.ok
        assert {e["entry"] for e in sentinel.events} & {"prefill_into",
                                                        "decode"}


@pytest.mark.slow
class TestLiveAnalysis:
    """The CI analysis job's live smoke, as a test: det sharded engine on
    the forced 4-device mesh — plan lints, HLO lints against the
    committed collective budget, and a mid-stream-refill stream_serve
    with zero post-warmup recompiles."""

    def test_live_det_clean(self):
        out = subprocess.run(
            [sys.executable, "-c", textwrap.dedent("""
                import os
                os.environ["XLA_FLAGS"] = \
                    "--xla_force_host_platform_device_count=4"
                os.environ["JAX_PLATFORMS"] = "cpu"
                import sys, json
                sys.path.insert(0, "src")
                from repro.analysis.__main__ import _live_child
                from repro.analysis.findings import findings_to_json
                print("FINDINGS " +
                      json.dumps(findings_to_json(_live_child("det"))))
            """)], cwd=REPO, capture_output=True, text=True,
            timeout=560)
        assert out.returncode == 0, (out.stdout[-500:], out.stderr[-2000:])
        line = [ln for ln in out.stdout.splitlines()
                if ln.startswith("FINDINGS ")][-1]
        findings = [Finding.from_json(d)
                    for d in json.loads(line[len("FINDINGS "):])]
        assert errors(findings) == [], findings_to_json(errors(findings))
        assert not [f for f in findings if f.rule == "serve.retrace"]


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

class TestCli:
    def test_all_goldens_gate_is_clean(self, tmp_path, capsys):
        from repro.analysis.__main__ import main

        out_json = tmp_path / "findings.json"
        assert main(["--all-goldens", "--json", str(out_json)]) == 0
        report = capsys.readouterr().out
        assert "repro.analysis: OK" in report
        data = json.loads(out_json.read_text())
        assert all(d["severity"] != "error" for d in data)

    def test_broken_manifest_fails_and_waiver_passes(self, tmp_path,
                                                     capsys):
        from repro.analysis.__main__ import main

        plan = ExecutionPlan.load(
            os.path.join(GOLDEN_DIR, "mnist_fc_det.json"))
        bad = copy.deepcopy(plan)
        row = [a for a in bad.layers if a.backend == "packed"][0]
        row.sharding = [None, "typo_axis"]
        path = str(tmp_path / "bad.json")
        bad.save(path)
        assert main(["--plan", path]) == 1
        assert "FAIL" in capsys.readouterr().out
        assert main(["--plan", path, "--waive", "plan.unknown_axis"]) == 0
