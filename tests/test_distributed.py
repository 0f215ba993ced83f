"""Distribution-layer tests: run under forced multi-device CPU in
subprocesses (so the main test process stays single-device).

Covers: small-mesh dry-run of train/serve steps (the in-CI proxy for the
512-chip dry-run), pipeline parallelism vs the serial oracle, sharding-rule
divisibility invariants, and distributed equivalence of the sharded train
step vs single-device execution.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs import base as cb
from repro.distributed.sharding import divisibility_report

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, timeout=560):
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         cwd=REPO, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, (out.stdout[-500:], out.stderr[-2000:])
    return out.stdout


class TestShardingRules:
    @pytest.mark.parametrize("arch", [a for a in cb.ARCH_IDS
                                      if a not in ("mnist_fc", "vgg16_cifar10")])
    def test_tp16_divisibility(self, arch):
        """The documented invariant: d_ff / q_dim / kv_dim shard cleanly
        over the 16-way model axis for every assigned arch."""
        cfg = cb.get_config(arch)
        rep = divisibility_report(cfg, 16)
        assert rep["d_ff"], (arch, cfg.d_ff)
        assert rep["q_dim"], (arch, cfg.q_dim)
        assert rep["kv_dim"], (arch, cfg.kv_dim)
        assert rep["d_inner"], (arch, cfg.d_inner)

    def test_params_pspecs_rank_safe(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.distributed.sharding import params_pspecs
        from repro.models import transformer as T

        cfg = cb.get_config("jamba_1_5_large", smoke=True)
        params = jax.eval_shape(lambda: T.init_lm(cfg, jax.random.key(0)))
        specs = params_pspecs(params, fsdp=True)
        for (path, leaf), (_, spec) in zip(
                jax.tree_util.tree_leaves_with_path(params),
                jax.tree_util.tree_leaves_with_path(
                    specs, is_leaf=lambda x: isinstance(x, P))):
            assert len(spec) <= leaf.ndim, (path, spec, leaf.shape)


class TestShardingHelpers:
    """Satellite coverage for the distributed/sharding.py helpers."""

    def test_shardctx_act_noop_without_mesh(self):
        import jax.numpy as jnp
        from repro.distributed.sharding import ShardCtx

        x = jnp.arange(12.0).reshape(2, 2, 3)
        sh = ShardCtx(mesh=None)
        assert sh.act(x, "btd") is x          # identity, no device state
        assert ShardCtx(mesh=None, enable=False).act(x, "btf") is x

    def test_make_mesh_axes_are_auto(self):
        """Every mesh comes from make_mesh, with Auto axes: under jax's
        default Explicit axes the embedding gather of the mesh-sharded
        serving path refuses to lower."""
        from jax.sharding import AxisType
        from repro.distributed.sharding import make_mesh

        mesh = make_mesh((1, 1), ("data", "model"))
        assert mesh.axis_names == ("data", "model")
        assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)

    def test_batch_axes_with_and_without_pod(self):
        from types import SimpleNamespace

        from repro.distributed.sharding import batch_axes

        assert batch_axes(None) == ("data",)
        single = SimpleNamespace(axis_names=("data", "model"))
        multi = SimpleNamespace(axis_names=("pod", "data", "model"))
        assert batch_axes(single) == ("data",)
        assert batch_axes(multi) == ("pod", "data")

    def test_leaf_pspec_matches_params_pspecs(self):
        """leaf_pspec is the single-leaf form of the tree mapper."""
        import jax
        from jax.sharding import PartitionSpec as P

        from repro.distributed.sharding import leaf_pspec, params_pspecs

        params = {"layers": {"attn": {"w_qkv": jax.ShapeDtypeStruct(
            (4, 64, 96), jax.numpy.float32)}}}
        tree = params_pspecs(params)
        assert tree["layers"]["attn"]["w_qkv"] == \
            leaf_pspec("layers/attn/w_qkv", 3)
        assert leaf_pspec("layers/attn/w_qkv", 3) == P(None, None, "model")
        assert leaf_pspec("layers/attn/w_o", 2) == P("model", None)
        assert leaf_pspec("layers/ln1/scale", 1) == P(None)  # replicated

    def test_shardctx_threads_through_apply_seams(self):
        """apply_linear/apply_conv2d constrain their OUTPUT through the
        sh/kind kwargs — whichever backend served the layer — and stay
        no-ops when sh or kind is absent."""
        import jax
        import jax.numpy as jnp

        from repro.models.layers import apply_conv2d, apply_linear

        calls = []

        class SpyCtx:
            def act(self, x, kind):
                calls.append((kind, x.shape))
                return x + 1.0

        w = jnp.ones((4, 3))
        x = jnp.ones((2, 4))
        base = apply_linear(w, x)
        got = apply_linear(w, x, sh=SpyCtx(), kind="btf")
        assert calls == [("btf", (2, 3))]
        assert float(jnp.abs(got - (base + 1.0)).max()) == 0.0
        assert apply_linear(w, x, sh=SpyCtx()) is not None  # kind=None: no-op
        assert calls == [("btf", (2, 3))]
        cw = jnp.ones((3, 3, 2, 5))
        cx = jnp.ones((1, 4, 4, 2))
        calls.clear()
        out = apply_conv2d(cw, cx, sh=SpyCtx(), kind="btd")
        assert calls == [("btd", out.shape)]

    def test_cache_pspecs_handle_empty_data_axes(self):
        """A pure tensor-parallel mesh has no data/pod axis: slot dims
        must replicate (entry None), not crash on the empty dp tuple."""
        from jax.sharding import PartitionSpec as P

        from repro.configs import base as cb
        from repro.models.transformer import cache_pspecs, cache_slot_axes

        for arch in ("starcoder2_3b", "mamba2_130m", "jamba_1_5_large"):
            cfg = cb.get_config(arch, smoke=True)
            specs = cache_pspecs(cfg, dp_axes=())
            assert set(specs) == set(cache_slot_axes(cfg))
            for name, axis in cache_slot_axes(cfg).items():
                spec = specs[name]
                assert len(spec) <= axis + 1 or spec[axis] is None, \
                    (arch, name)
            assert specs["pos"] == P(None)

    def test_spec_json_roundtrip(self):
        from jax.sharding import PartitionSpec as P

        from repro.distributed.sharding import spec_from_json, spec_to_json

        for spec in (P(), P(None, "model"), P(("pod", "data"), None, "model")):
            assert spec_from_json(spec_to_json(spec)) == spec


class TestSmallMeshDryRun:
    """8-device (2 data x 4 model) version of the production dry-run."""

    def test_train_step_lowers_and_runs(self):
        out = _run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import sys; sys.path.insert(0, "src")
            import json
            import jax, jax.numpy as jnp
            from repro.configs import base as cb
            from repro.core.policy import DEFAULT_POLICY
            from repro.distributed.sharding import (ShardCtx, make_mesh,
                                                    mesh_context, params_pspecs)
            from repro.launch import specs as SP
            from repro.models import transformer as T
            from repro.optim import schedules
            from repro.optim.sgd import sgd_momentum
            from repro.train import steps as ST
            from jax.sharding import NamedSharding, PartitionSpec as P

            mesh = make_mesh((2, 4), ("data", "model"))
            cfg = cb.get_config("starcoder2_3b", smoke=True)
            sh = ShardCtx(mesh)
            opt = sgd_momentum(schedules.constant(1e-2))
            step = ST.make_train_step(ST.make_lm_loss(cfg, sh), opt, "det",
                                      DEFAULT_POLICY)
            params = T.init_lm(cfg, jax.random.key(0))
            state = ST.init_train_state(params, opt)
            st_ps = SP.state_pspecs(state["params"], mesh, fsdp=False)
            st_ps = SP.sanitize_pspecs(jax.eval_shape(lambda: state), st_ps, mesh)
            ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                        is_leaf=lambda x: isinstance(x, P))
            batch = {"tokens": jax.random.randint(jax.random.key(1), (8, 33),
                                                  0, cfg.vocab_size)}
            with mesh_context(mesh):
                jitted = jax.jit(step, in_shardings=(ns(st_ps),
                                 ns({"tokens": P(("data",), None)})),
                                 out_shardings=(ns(st_ps), None))
                state2, metrics = jitted(state, batch)
            # run ACTUALLY executes on 8 devices (not just lowers)
            print(json.dumps({"loss": float(metrics["loss"]),
                              "step": int(state2["step"])}))
        """)
        res = json.loads(out.strip().splitlines()[-1])
        assert res["step"] == 1
        assert res["loss"] > 0

    def test_sharded_equals_single_device(self):
        """Same step, same data: 8-device SPMD == single device."""
        out = _run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import sys; sys.path.insert(0, "src")
            import json
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import base as cb
            from repro.core.policy import DEFAULT_POLICY
            from repro.distributed.sharding import ShardCtx, make_mesh, mesh_context
            from repro.launch import specs as SP
            from repro.models import transformer as T
            from repro.optim import schedules
            from repro.optim.sgd import sgd_momentum
            from repro.train import steps as ST
            from jax.sharding import NamedSharding, PartitionSpec as P

            cfg = cb.get_config("starcoder2_3b", smoke=True)
            opt = sgd_momentum(schedules.constant(1e-2))
            params = T.init_lm(cfg, jax.random.key(0))
            batch = {"tokens": jax.random.randint(jax.random.key(1), (8, 33),
                                                  0, cfg.vocab_size)}
            # single device
            step0 = ST.make_train_step(ST.make_lm_loss(cfg), opt, "det",
                                       DEFAULT_POLICY)
            s0 = ST.init_train_state(jax.tree.map(jnp.copy, params), opt)
            s0, m0 = jax.jit(step0)(s0, batch)
            # sharded
            mesh = make_mesh((2, 4), ("data", "model"))
            sh = ShardCtx(mesh)
            step1 = ST.make_train_step(ST.make_lm_loss(cfg, sh), opt, "det",
                                       DEFAULT_POLICY)
            s1 = ST.init_train_state(jax.tree.map(jnp.copy, params), opt)
            st_ps = SP.state_pspecs(s1["params"], mesh, fsdp=False)
            st_ps = SP.sanitize_pspecs(jax.eval_shape(lambda: s1), st_ps, mesh)
            ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                        is_leaf=lambda x: isinstance(x, P))
            with mesh_context(mesh):
                s1, m1 = jax.jit(step1, in_shardings=(ns(st_ps),
                    ns({"tokens": P(("data",), None)})),
                    out_shardings=(ns(st_ps), None))(s1, batch)
            d = max(float(jnp.abs(a.astype(jnp.float32) -
                                  b.astype(jnp.float32)).max())
                    for a, b in zip(jax.tree.leaves(s0["params"]),
                                    jax.tree.leaves(s1["params"]))
                    if hasattr(a, "astype"))
            print(json.dumps({"loss0": float(m0["loss"]),
                              "loss1": float(m1["loss"]), "max_param_diff": d}))
        """)
        res = json.loads(out.strip().splitlines()[-1])
        assert abs(res["loss0"] - res["loss1"]) < 1e-3, res
        assert res["max_param_diff"] < 5e-3, res

    def test_serve_decode_lowers(self):
        out = _run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import sys; sys.path.insert(0, "src")
            sys.argv = ["dryrun", "--arch", "h2o_danube_3_4b", "--shape",
                        "decode_32k", "--mesh", "single", "--smoke",
                        "--out", "/tmp/dr_smoke_test", "--force"]
            # monkeypatch the production mesh to the 8-device debug mesh
            import jax
            from repro.distributed.sharding import make_mesh
            from repro.launch import mesh as M
            M.make_production_mesh = lambda multi_pod=False: (
                make_mesh((2, 2, 2), ("pod", "data", "model"))
                if multi_pod else make_mesh((2, 4), ("data", "model")))
            from repro.launch import dryrun
            dryrun.make_production_mesh = M.make_production_mesh
            dryrun.main()
        """)
        assert "1 ok" in out


class TestMeshShardedServing:
    """Tentpole acceptance: tensor-parallel execution plans through the
    step-level decode engine on a forced 4-device CPU mesh."""

    def test_stream_serve_bit_identical_and_placed(self):
        """For det and xnor plans on a 2x2 ("data", "model") mesh: greedy
        stream_serve output is bit-identical to the single-device engine
        through a mid-stream slot refill (5 requests, 2 slots, mixed
        max_new), packed weight words shard over "model" on the out-channel
        dim, and the decode cache shards slots over "data"."""
        out = _run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import sys; sys.path.insert(0, "src")
            import json
            import jax, numpy as np
            from repro.distributed.sharding import make_mesh
            from jax.sharding import PartitionSpec as P
            from repro.configs import base as cb
            from repro.core.policy import DEFAULT_POLICY
            from repro.engine import compile_plan
            from repro.models import transformer as T
            from repro.serve.batcher import SlotBatcher
            from repro.serve.engine import ServeEngine, stream_serve

            mesh = make_mesh((2, 2), ("data", "model"))
            cfg = cb.get_config("starcoder2_3b", smoke=True)
            params = T.init_lm(cfg, jax.random.key(0))

            def run(engine):
                rng = np.random.default_rng(0)
                b = SlotBatcher(2, 8)
                for m in [3, 5, 2, 4, 3]:   # 5 requests > 2 slots: refill
                    b.submit(rng.integers(0, cfg.vocab_size, 8), m)
                stream_serve(engine, b)
                return {int(r.uid): list(map(int, r.generated))
                        for r in b.completed}

            identical = {}
            for mode in ("det", "xnor"):
                plan = compile_plan(params, DEFAULT_POLICY, mode, warn=False,
                                    mesh=mesh)
                packed = plan.pack(params)
                single = run(ServeEngine(cfg, packed))
                eng = ServeEngine(cfg, packed, mesh=mesh, plan=plan)
                identical[mode] = run(eng) == single
            # placement facts (last engine): packed words TP on out-channel
            w = eng.params["layers"]["attn"]["w_qkv"]
            wspec = w.packed.sharding.spec
            state = eng.init_decode(2, 8, 4)
            kspec = state.cache["k"].sharding.spec
            # pure-TP mesh (no data axis): placement must not crash and
            # slot dims replicate
            tp_mesh = make_mesh((4,), ("model",))
            tp_state = ServeEngine(cfg, packed, mesh=tp_mesh).init_decode(
                2, 8, 4)
            tp_pos = list(tp_state.cache["pos"].sharding.spec)
            print(json.dumps({
                "identical": identical,
                "w_qkv_spec": [None if e is None else str(e) for e in wspec],
                "k_model_sharded": "model" in kspec,
                "k_data_axis": kspec[1] if len(kspec) > 1 else None,
                "pos_spec": list(state.cache["pos"].sharding.spec),
                "tp_pos_replicated": all(e is None for e in tp_pos),
            }))
        """)
        res = json.loads(out.strip().splitlines()[-1])
        assert res["identical"] == {"det": True, "xnor": True}
        # packed int32 words: "model" on the out-channel (last) dim only —
        # the word (K//32) dim is never split
        assert res["w_qkv_spec"][-1] == "model"
        assert all(e is None for e in res["w_qkv_spec"][:-1])
        # decode cache: slots over "data"
        assert res["k_data_axis"] == "data"
        assert res["pos_spec"] == ["data"]
        assert res["tp_pos_replicated"]

    def test_chunked_decode_bit_identical_sharded(self):
        """The multi-step inner loop (``decode_chunk > 1``: d decode steps
        under one lax.scan, one host crossing per chunk) emits streams
        bit-identical to the single-step single-device loop for det AND
        xnor on the 2x2 mesh, through a mid-stream slot refill (5 requests,
        2 slots, mixed max_new — chunk clipping to ``min_remaining`` must
        land every completion exactly on a chunk boundary)."""
        out = _run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import sys; sys.path.insert(0, "src")
            import json
            import jax, numpy as np
            from repro.distributed.sharding import make_mesh
            from repro.configs import base as cb
            from repro.core.policy import DEFAULT_POLICY
            from repro.engine import compile_plan
            from repro.models import transformer as T
            from repro.serve.batcher import SlotBatcher
            from repro.serve.engine import ServeEngine, stream_serve

            mesh = make_mesh((2, 2), ("data", "model"))
            cfg = cb.get_config("starcoder2_3b", smoke=True)
            params = T.init_lm(cfg, jax.random.key(0))

            def run(engine, chunk):
                rng = np.random.default_rng(0)
                b = SlotBatcher(2, 8)
                for m in [3, 5, 2, 4, 3]:   # 5 requests > 2 slots: refill
                    b.submit(rng.integers(0, cfg.vocab_size, 8), m)
                steps = stream_serve(engine, b, decode_chunk=chunk)
                return steps, {int(r.uid): list(map(int, r.generated))
                               for r in b.completed}

            res = {}
            for mode in ("det", "xnor"):
                plan = compile_plan(params, DEFAULT_POLICY, mode,
                                    warn=False, mesh=mesh)
                packed = plan.pack(params)
                s1, single = run(ServeEngine(cfg, packed), 1)
                eng = ServeEngine(cfg, packed, mesh=mesh, plan=plan)
                s3, chunked = run(eng, 3)
                res[mode] = {"identical": chunked == single,
                             "same_steps": s1 == s3}
            print(json.dumps(res))
        """)
        res = json.loads(out.strip().splitlines()[-1])
        for mode in ("det", "xnor"):
            assert res[mode]["identical"], mode
            assert res[mode]["same_steps"], mode

    def test_ensemble_replica_axis_sharded_bit_identical(self):
        """Ensemble acceptance: K=4 stochastic replicas with the replica
        axis sharded over the plan's ``replica_axis`` column ("data" and
        "model" both exercised) on a forced 4-device mesh stream greedy
        tokens bit-identical to the single-device ensemble engine, and the
        stacked packed words actually carry the replica axis on dim 0."""
        out = _run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import sys; sys.path.insert(0, "src")
            import json
            import jax, numpy as np
            from repro.distributed.sharding import make_mesh
            from repro.configs import base as cb
            from repro.core.policy import DEFAULT_POLICY
            from repro.engine import compile_plan
            from repro.models import transformer as T
            from repro.serve.batcher import SlotBatcher
            from repro.serve.engine import ServeEngine, stream_serve
            from repro.stoch import place_replicas, sample_replicas

            cfg = cb.get_config("starcoder2_3b", smoke=True)
            params = T.init_lm(cfg, jax.random.key(0))

            def run(engine):
                rng = np.random.default_rng(0)
                b = SlotBatcher(2, 8)
                for m in [3, 5, 2]:
                    b.submit(rng.integers(0, cfg.vocab_size, 8), m)
                stream_serve(engine, b)
                return {int(r.uid): list(map(int, r.generated))
                        for r in b.completed}

            res = {}
            for rax, shape, names in [("data", (4,), ("data",)),
                                      ("model", (2, 2), ("data", "model"))]:
                mesh = make_mesh(shape, names)
                plan = compile_plan(params, DEFAULT_POLICY, "stoch",
                                    warn=False, mesh=mesh, replica_axis=rax)
                rs = sample_replicas(params, plan, jax.random.key(1), 4)
                single = run(ServeEngine(cfg, None, ensemble=rs))
                eng = ServeEngine(cfg, None, ensemble=rs, mesh=mesh,
                                  plan=plan)
                stacked_w = eng._replicas.stacked["layers/attn/w_qkv"]
                res[rax] = {
                    "identical": run(eng) == single,
                    "lead_spec": str(stacked_w.packed.sharding.spec[0]),
                }
            print(json.dumps(res))
        """)
        res = json.loads(out.strip().splitlines()[-1])
        for rax in ("data", "model"):
            assert res[rax]["identical"], rax
            assert res[rax]["lead_spec"] == rax

    def test_plan_manifest_roundtrips_sharding_column(self, tmp_path):
        """Satellite of the tentpole: the sharding column survives
        save/load and the loaded plan still packs identically (no mesh
        needed — the column is axis names)."""
        import jax

        from repro.configs import base as cb
        from repro.engine import ExecutionPlan, compile_plan
        from repro.models import transformer as T

        from repro.core.policy import DEFAULT_POLICY

        cfg = cb.get_config("starcoder2_3b", smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        plan = compile_plan(params, DEFAULT_POLICY, "det", warn=False)
        path = str(tmp_path / "plan.json")
        plan.save(path)
        loaded = ExecutionPlan.load(path)
        assert loaded.to_json() == plan.to_json()
        # binary backends: "model" on the out-channel dim
        row = loaded["layers/attn/w_qkv"]
        assert row.backend == "packed"
        assert row.sharding == [None, None, "model"]
        from jax.sharding import PartitionSpec as P
        assert row.pspec == P(None, None, "model")
        # dense leaves follow the Megatron rules (w_o is row-parallel when
        # dense or xnor — exact integer partial sums — and out-channel
        # under packed, whose f32 partials must not cross an all-reduce);
        # the tied embedding is vocab-parallel: (V, D) sharded on V
        assert loaded["embed/embedding"].sharding == ["model", None]
        assert loaded["layers/ln1/scale"].sharding == [None, None]


class TestPipelineParallel:
    def test_gpipe_matches_serial_oracle(self):
        out = _run("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import sys; sys.path.insert(0, "src")
            import json
            import jax, jax.numpy as jnp, numpy as np
            from repro.distributed.sharding import make_mesh
            from repro.distributed.pipeline_parallel import (
                pipeline_forward, reference_forward, run_pipeline)

            n_stages, n_micro, mb, d = 4, 8, 2, 16
            mesh = make_mesh((n_stages,), ("stage",))
            def stage_fn(p, x):
                return jnp.tanh(x @ p["w"] + p["b"])
            params = {
                "w": jax.random.normal(jax.random.key(0), (n_stages, d, d)) * 0.5,
                "b": jax.random.normal(jax.random.key(1), (n_stages, d)) * 0.1,
            }
            micro = jax.random.normal(jax.random.key(2), (n_micro, mb, d))
            got = run_pipeline(mesh, stage_fn, params, micro)
            want = reference_forward(stage_fn, params, micro)
            err = float(jnp.abs(got - want).max())
            print(json.dumps({"err": err}))
        """)
        res = json.loads(out.strip().splitlines()[-1])
        assert res["err"] < 1e-5, res
