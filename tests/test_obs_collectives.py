"""Static collective audit: exact per-step collective counts for the
sharded serving programs (golden-checked), the audit vs hlo_analysis
cross-check on a hand-built sharded program, and the plan_report
prediction column.

Multi-device pieces run in subprocesses with forced host devices (device
count is fixed at backend init), mirroring tests/test_distributed.py.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.obs.collectives import (ACT_BYTES, CollectiveAudit, audit_hlo,
                                   format_audit, predict_row_collective)

GOLDEN = os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks",
                      "golden_plans", "collectives.json")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, timeout=560):
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         cwd=REPO, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, (out.stdout[-500:], out.stderr[-2000:])
    return out.stdout


class TestCollectiveAudit:
    def test_json_round_trip(self):
        a = CollectiveAudit("decode_step",
                            counts={"all-reduce": 3, "all-gather": 1},
                            bytes={"all-reduce": 96.0, "all-gather": 32.0},
                            reshard_copies=2, reshard_copy_bytes=64.0)
        b = CollectiveAudit.from_json(json.loads(json.dumps(a.to_json())))
        assert b == a
        assert a.total_count == 4 and a.total_bytes == 128.0
        assert "all-reduce x3" in a.summary()

    def test_format_audit_table(self):
        a = CollectiveAudit("decode_step", counts={"all-reduce": 3},
                            bytes={"all-reduce": 96.0}, reshard_copies=1,
                            reshard_copy_bytes=8.0)
        table = format_audit({"decode_step": a})
        assert "all-reduce" in table and "reshard-copy" in table
        assert table.splitlines()[0].startswith("entry")

    def test_empty_program_audits_clean(self):
        """A trivial single-device program has no collectives at all."""
        import jax
        import jax.numpy as jnp

        compiled = jax.jit(lambda x: x * 2.0).lower(
            jnp.ones((4, 4))).compile()
        a = audit_hlo(compiled.as_text(), entry="double")
        assert a.total_count == 0 and a.counts == {}


class TestPredictRowCollective:
    def test_out_channel_split_predicts_all_gather(self):
        c = predict_row_collective([None, "model"], (256, 512), batch=8)
        assert c["kind"] == "all-gather" and c["axes"] == ["model"]
        assert c["bytes_per_app"] == 8 * 512 * ACT_BYTES
        assert c["parts"] is None        # unknown without axis sizes
        c = predict_row_collective([None, "model"], (256, 512), batch=8,
                                   axis_sizes={"model": 4, "data": 2})
        assert c["parts"] == 4

    def test_contraction_split_predicts_all_reduce(self):
        c = predict_row_collective(["model", None], (256, 512), batch=4)
        assert c["kind"] == "all-reduce" and c["axes"] == ["model"]
        assert c["bytes_per_app"] == 4 * 512 * ACT_BYTES

    def test_batch_axes_and_trivial_splits_predict_nothing(self):
        assert predict_row_collective(["data", None], (256, 512)) is None
        assert predict_row_collective(None, (256, 512)) is None
        assert predict_row_collective([None, "model"], (512,)) is None
        assert predict_row_collective([None, "model"], (256, 512),
                                      axis_sizes={"model": 1}) is None

    def test_plan_report_carries_collectives_column(self):
        """A mesh-compiled plan's report predicts a collective for every
        TP-sharded row and formats it into the table."""
        import jax
        from repro.distributed.sharding import make_mesh

        from repro.configs import base as cb
        from repro.core.policy import DEFAULT_POLICY
        from repro.engine import compile_plan
        from repro.engine.plan import format_plan_table, plan_report
        from repro.models import transformer as T

        mesh = make_mesh((1, 1), ("data", "model"))
        cfg = cb.get_config("starcoder2_3b", smoke=True)
        params = jax.eval_shape(lambda: T.init_lm(cfg, jax.random.key(0)))
        plan = compile_plan(params, DEFAULT_POLICY, "det", warn=False,
                            mesh=mesh)
        rows = plan_report(plan, batch=8)
        predicted = [r for r in rows if r["collectives"] is not None]
        assert predicted, "no TP-sharded row produced a prediction"
        for r in predicted:
            c = r["collectives"]
            assert c["kind"] in ("all-gather", "all-reduce")
            assert c["bytes_per_app"] == 8 * r["n"] * ACT_BYTES
        table = format_plan_table(rows)
        assert "collectives" in table.splitlines()[0]
        assert "all-gather@model" in table
        # axis size 1 resolves every prediction away (nothing to gather)
        rows1 = plan_report(plan, batch=8,
                            axis_sizes={"data": 1, "model": 1})
        assert all(r["collectives"] is None for r in rows1)


class TestAuditVsHloAnalysis:
    def test_psum_matmul_audit_is_exact(self):
        """Cross-check on an unscanned hand-built sharded program: the
        audit must agree with hlo_analysis kind-for-kind AND with the
        analytic expectation — a contraction-sharded matmul needs exactly
        one all-reduce of the (M, N) f32 output."""
        out = _run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            os.environ["JAX_PLATFORMS"] = "cpu"
            import sys, json
            sys.path.insert(0, "src")
            import jax, jax.numpy as jnp
            from repro.distributed.sharding import make_mesh
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.core import hlo_analysis as H
            from repro.obs.collectives import audit_hlo

            mesh = make_mesh((4,), ("model",))
            x = jax.device_put(jnp.ones((8, 64), jnp.float32),
                               NamedSharding(mesh, P(None, "model")))
            w = jax.device_put(jnp.ones((64, 16), jnp.float32),
                               NamedSharding(mesh, P("model", None)))
            out_s = NamedSharding(mesh, P(None, None))
            f = jax.jit(lambda x, w: x @ w, out_shardings=out_s)
            text = f.lower(x, w).compile().as_text()
            audit = audit_hlo(text, entry="psum_matmul")
            cost = H.analyze(text)
            print("RESULT " + json.dumps({
                "audit": audit.to_json(),
                "hlo_counts": {k: int(v)
                               for k, v in cost.collective_count.items()},
                "hlo_bytes": dict(cost.collective_bytes_by_kind),
            }))
        """)
        res = json.loads([ln for ln in out.splitlines()
                          if ln.startswith("RESULT ")][-1][len("RESULT "):])
        audit = CollectiveAudit.from_json(res["audit"])
        # agreement with the hlo_analysis walk, kind for kind
        assert audit.counts == res["hlo_counts"]
        assert audit.bytes == pytest.approx(res["hlo_bytes"])
        # analytic exactness: one all-reduce of the f32 (8, 16) output
        assert audit.counts == {"all-reduce": 1}
        assert audit.bytes["all-reduce"] == 8 * 16 * 4


class TestGoldenShardedAudit:
    """The ROADMAP success metric, stated as a test: the det and xnor
    sharded golden plans execute an exact, known number of collectives per
    decode step on the 2x2 ("data", "model") mesh."""

    @pytest.fixture(scope="class")
    def measured(self):
        out = _run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            os.environ["JAX_PLATFORMS"] = "cpu"
            import sys, json
            sys.path.insert(0, "src"); sys.path.insert(0, ".")
            from benchmarks.check_collectives import _child
            print("RESULT " + json.dumps(_child()))
        """)
        return json.loads([ln for ln in out.splitlines()
                           if ln.startswith("RESULT ")][-1][len("RESULT "):])

    def test_matches_committed_golden(self, measured):
        with open(GOLDEN) as f:
            golden = json.load(f)
        assert golden["mesh"] == {"shape": [2, 2],
                                  "axes": ["data", "model"]}
        assert measured == golden["audits"]

    def test_decode_step_exact_counts(self, measured):
        """The headline numbers, asserted inline — after the decode-mode
        ShardCtx overhaul (replicated decode activations, model-free cache,
        vocab-parallel tied embedding, deferred logits gather, K/V rows
        scattered per device into the carried cache, outputs pinned to the
        init_decode placement; docs/ARCHITECTURE.md §Decode-step
        collective budget) a decode step runs 10 (det) / 12 (xnor)
        collectives, down from the 41 the seq-parallel training layout
        cost. All remaining traffic is activation-sized: det is 8
        per-layer all-gathers + the deferred logits gather + the
        vocab-parallel embed-lookup all-reduce; xnor swaps four of the
        gathers for exact integer popcount all-reduces (row-parallel
        down-projections) and pays two collective-permutes splitting the
        qkv output."""
        det = CollectiveAudit.from_json(measured["det"]["decode_step"])
        assert det.counts == {"all-gather": 9, "all-reduce": 1}
        assert det.total_count == 10
        assert det.bytes["all-gather"] == 10240.0
        assert det.bytes["all-reduce"] == 1024.0
        xnor = CollectiveAudit.from_json(measured["xnor"]["decode_step"])
        assert xnor.counts == {"all-gather": 5, "all-reduce": 5,
                               "collective-permute": 2}
        assert xnor.total_count == 12
        # no weight-sized traffic anywhere: the largest single transfer is
        # well under the 131072-byte tied-embedding table gather the old
        # layout paid every step
        for mode in ("det", "xnor"):
            a = CollectiveAudit.from_json(measured[mode]["decode_step"])
            assert a.total_bytes < 40_000
            assert a.reshard_copy_bytes < 65_536

    def test_prefill_exact_counts(self, measured):
        pre = CollectiveAudit.from_json(measured["det"]["prefill_into"])
        assert pre.counts == {"all-gather": 1, "all-reduce": 15,
                              "all-to-all": 8, "collective-permute": 8}
        assert pre.total_count == 32
