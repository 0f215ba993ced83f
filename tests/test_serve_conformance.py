"""Serving conformance harness: greedy streams are bit-identical to
one-shot ``generate`` across the serving configuration cross-product.

The invariant every serving PR inherits: however a request's prompt gets
into its slot — whole-prompt ``prefill_into``, chunked prefill through the
fused ``decode_prefill`` step, or a prefix-cache splice (cold miss or
mid-stream hit) — and however the engine is built — {dense, det, xnor}
plan, single device or a forced 4-device ("data", "model") mesh, K=1
ensemble — the per-request greedy token streams must equal the one-shot
oracle exactly. The forced-mesh rows run in subprocesses (marked ``slow``;
CI runs them as their own step).
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as cb
from repro.core.policy import DEFAULT_POLICY
from repro.models import transformer as T
from repro.serve import PrefixCache, ServeEngine, SlotBatcher, stream_serve
from repro.serve.engine import pack_params

ARCH = "starcoder2_3b"
PROMPT_LEN = 8
MAX_NEWS = [3, 5, 2, 4, 3]
CAP = 5

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def engines():
    """One engine per plan mode, built lazily and shared across the
    matrix (engine construction dominates test wall-clock)."""
    cache = {}

    def get(plan_mode):
        if plan_mode not in cache:
            cfg = cb.get_config(ARCH, smoke=True)
            params = T.init_lm(cfg, jax.random.key(0))
            if plan_mode != "dense":
                params = pack_params(params, DEFAULT_POLICY, plan_mode)
            cache[plan_mode] = (cfg, ServeEngine(cfg, params))
        return cache[plan_mode]

    return get


def _prompts(cfg, shared_prefix=True):
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, cfg.vocab_size,
                           size=(len(MAX_NEWS), PROMPT_LEN)).astype(np.int32)
    if shared_prefix:
        # request 3 repeats request 0's prompt: with a prefix cache it is
        # admitted MID-STREAM as a full-prompt hit (zero prefill chunks)
        prompts[3] = prompts[0]
    return prompts


def _oracle(engine, prompts, max_news=MAX_NEWS):
    return {i: np.asarray(engine.generate(jnp.asarray(p)[None],
                                          m).tokens)[0].tolist()
            for i, (p, m) in enumerate(zip(prompts, max_news))}


def _stream(engine, prompts, *, n_slots=2, max_news=MAX_NEWS,
            prompt_len=PROMPT_LEN, cap=CAP, **kw):
    b = SlotBatcher(n_slots, prompt_len)
    for p, m in zip(prompts, max_news):
        b.submit(p, m)
    stream_serve(engine, b, max_new_cap=cap, **kw)
    assert b.idle and len(b.completed) == len(max_news)
    return {r.uid: list(r.generated) for r in b.completed}


class TestSingleDeviceMatrix:
    @pytest.mark.parametrize("prefill", ["whole", "chunked"])
    @pytest.mark.parametrize("plan_mode", ["dense", "det", "xnor"])
    def test_stream_matches_generate(self, engines, plan_mode, prefill):
        """{dense, det, xnor} x {whole-prompt, chunked} without a prefix
        cache: streams through mid-stream slot refill == generate."""
        cfg, engine = engines(plan_mode)
        prompts = _prompts(cfg)
        want = _oracle(engine, prompts)
        kw = {"prefill_chunk": 3} if prefill == "chunked" else {}
        assert _stream(engine, prompts, **kw) == want

    @pytest.mark.parametrize("prefill", ["whole", "chunked"])
    @pytest.mark.parametrize("plan_mode", ["dense", "det", "xnor"])
    def test_prefix_cache_miss_then_hit(self, engines, plan_mode, prefill):
        """Cold pass (misses + ONE mid-stream full hit from the duplicate
        prompt), then a fully-warm pass where every admission is a prefix
        hit. Both passes bit-identical to generate."""
        cfg, engine = engines(plan_mode)
        prompts = _prompts(cfg)
        want = _oracle(engine, prompts)
        pc = PrefixCache()
        chunk = 3 if prefill == "chunked" else 0
        assert _stream(engine, prompts, prefill_chunk=chunk,
                       prefix_cache=pc) == want
        assert pc.hits >= 1, "mid-stream duplicate-prompt hit missing"
        cold_hits = pc.hits
        assert _stream(engine, prompts, prefill_chunk=chunk,
                       prefix_cache=pc) == want
        assert pc.hits >= cold_hits + len(MAX_NEWS)
        assert pc.evictions == 0


class TestFamilyConformance:
    @pytest.mark.parametrize("arch", ["mamba2_130m", "jamba_1_5_large",
                                      "h2o_danube_3_4b"])
    def test_chunked_prefix_stream_per_family(self, arch):
        """Chunked prefill + prefix reuse across the non-uniform cache
        families (ssm / hybrid / sliding-window): a partially-prefilled
        slot is a first-class cache state for each of them."""
        cfg = cb.get_config(arch, smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        engine = ServeEngine(cfg, params)
        prompts = _prompts(cfg)
        want = _oracle(engine, prompts)
        pc = PrefixCache()
        assert _stream(engine, prompts, prefill_chunk=3,
                       prefix_cache=pc) == want
        assert pc.hits >= 1

    def test_sliding_window_ring_wrap(self):
        """Chunk boundaries crossing the ring-buffer wrap: window 6 with a
        12-token prompt makes the chunked writes wrap mid-prefill, so the
        age-based cache masks and the post-attention ring write are
        exercised on both sides of the seam."""
        cfg = dataclasses.replace(cb.get_config("h2o_danube_3_4b",
                                                smoke=True),
                                  sliding_window=6)
        params = T.init_lm(cfg, jax.random.key(0))
        engine = ServeEngine(cfg, params)
        rng = np.random.default_rng(1)
        prompts = rng.integers(1, cfg.vocab_size, size=(3, 12)).astype(
            np.int32)
        max_news = [3, 4, 2]
        want = _oracle(engine, prompts, max_news)
        got = _stream(engine, prompts, max_news=max_news, prompt_len=12,
                      cap=4, prefill_chunk=5)
        assert got == want


class TestEnsembleConformance:
    def test_k1_ensemble_chunked_prefix_stream(self):
        """K=1 ensemble serving degrades to the single-sample path, so
        chunked prefill + prefix reuse must hold there too."""
        from repro.engine import compile_plan
        from repro.stoch import sample_replicas

        cfg = cb.get_config(ARCH, smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        plan = compile_plan(params, DEFAULT_POLICY, "stoch", warn=False)
        rs = sample_replicas(params, plan, jax.random.key(7), 1)
        engine = ServeEngine(cfg, None, ensemble=rs)
        prompts = _prompts(cfg)
        want = _oracle(engine, prompts)
        pc = PrefixCache()
        assert _stream(engine, prompts, prefill_chunk=3,
                       prefix_cache=pc) == want
        assert pc.hits >= 1

    def test_k2_ensemble_rejects_chunked_prefill(self):
        """K>=2 replica serving prefills whole prompts; asking for chunked
        prefill must fail loudly, not silently fall back."""
        from repro.engine import compile_plan
        from repro.stoch import sample_replicas

        cfg = cb.get_config(ARCH, smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        plan = compile_plan(params, DEFAULT_POLICY, "stoch", warn=False)
        rs = sample_replicas(params, plan, jax.random.key(7), 2)
        engine = ServeEngine(cfg, None, ensemble=rs)
        b = SlotBatcher(2, PROMPT_LEN)
        b.submit(np.arange(PROMPT_LEN), 2)
        with pytest.raises(NotImplementedError, match="single-sample"):
            stream_serve(engine, b, prefill_chunk=3)


def _run(code: str, timeout=560):
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         cwd=REPO, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, (out.stdout[-500:], out.stderr[-2000:])
    return out.stdout


@pytest.mark.slow
class TestForcedMeshMatrix:
    """Forced 4-device CPU mesh rows of the matrix (subprocess so the main
    test process stays single-device)."""

    @pytest.mark.parametrize("mode", ["det", "xnor"])
    def test_sharded_chunked_prefix_stream(self, mode):
        out = _run(f"""
            import os
            os.environ["JAX_PLATFORMS"] = "cpu"
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import sys
            sys.path.insert(0, "src")
            import numpy as np
            import jax, jax.numpy as jnp
            from repro.distributed.sharding import make_mesh
            from repro.configs import base as cb
            from repro.core.policy import DEFAULT_POLICY
            from repro.engine import compile_plan
            from repro.models import transformer as T
            from repro.serve import (PrefixCache, ServeEngine, SlotBatcher,
                                     stream_serve)

            cfg = cb.get_config("{ARCH}", smoke=True)
            params = T.init_lm(cfg, jax.random.key(0))
            plan = compile_plan(params, DEFAULT_POLICY, "{mode}", warn=False)
            packed = plan.pack(params)
            oracle_eng = ServeEngine(cfg, packed)
            mesh = make_mesh((2, 2), ("data", "model"))
            eng = ServeEngine(cfg, packed, mesh=mesh, plan=plan)

            rng = np.random.default_rng(0)
            prompts = rng.integers(1, cfg.vocab_size,
                                   size=(5, 8)).astype(np.int32)
            # request 4 queues behind the 4 slots, so by its admission
            # prompt 0's full snapshot exists: a mid-stream prefix hit
            # (request 3 would be admitted in the SAME refill as 0)
            prompts[4] = prompts[0]
            max_news = [3, 5, 2, 4, 3]
            want = {{i: np.asarray(oracle_eng.generate(
                        jnp.asarray(p)[None], m).tokens)[0].tolist()
                    for i, (p, m) in enumerate(zip(prompts, max_news))}}
            pc = PrefixCache()
            b = SlotBatcher(4, 8)
            for p, m in zip(prompts, max_news):
                b.submit(p, m)
            stream_serve(eng, b, max_new_cap=5, prefill_chunk=3,
                         prefix_cache=pc)
            got = {{r.uid: list(r.generated) for r in b.completed}}
            assert got == want, (got, want)
            assert pc.hits >= 1
            print("MESH_OK")
        """)
        assert "MESH_OK" in out

    def test_sharded_whole_prompt_stream_dense(self):
        """Dense plan on the forced mesh, whole-prompt path: the matrix's
        {single-device vs mesh} axis is covered for the legacy admission
        path too."""
        out = _run("""
            import os
            os.environ["JAX_PLATFORMS"] = "cpu"
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import sys
            sys.path.insert(0, "src")
            import numpy as np
            import jax, jax.numpy as jnp
            from repro.distributed.sharding import make_mesh
            from repro.configs import base as cb
            from repro.models import transformer as T
            from repro.serve import ServeEngine, SlotBatcher, stream_serve

            cfg = cb.get_config("starcoder2_3b", smoke=True)
            params = T.init_lm(cfg, jax.random.key(0))
            oracle_eng = ServeEngine(cfg, params)
            mesh = make_mesh((2, 2), ("data", "model"))
            eng = ServeEngine(cfg, params, mesh=mesh)

            rng = np.random.default_rng(0)
            prompts = rng.integers(1, cfg.vocab_size,
                                   size=(5, 8)).astype(np.int32)
            max_news = [3, 5, 2, 4, 3]
            want = {i: np.asarray(oracle_eng.generate(
                        jnp.asarray(p)[None], m).tokens)[0].tolist()
                    for i, (p, m) in enumerate(zip(prompts, max_news))}
            b = SlotBatcher(4, 8)
            for p, m in zip(prompts, max_news):
                b.submit(p, m)
            stream_serve(eng, b, max_new_cap=5)
            got = {r.uid: list(r.generated) for r in b.completed}
            assert got == want, (got, want)
            print("MESH_OK")
        """)
        assert "MESH_OK" in out
