"""Serving path: packed-weight inference equivalence, engine generation,
step-level continuous batching parity, slot batcher invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as cb
from repro.core import binarize as B
from repro.core.policy import DEFAULT_POLICY
from repro.models import transformer as T
from repro.models.layers import PackedLinear, XnorConv, XnorLinear, apply_linear
from repro.serve.batcher import SlotBatcher
from repro.serve.engine import (ServeEngine, pack_params, packed_param_bytes,
                                stream_serve)


class TestPackParams:
    def test_packed_equals_binarized_dense(self):
        """unscaled packed inference == dense inference on det-binarized
        weights (the Alg.-1 inference network), per arch template."""
        for arch in ("starcoder2_3b", "mamba2_130m"):
            cfg = cb.get_config(arch, smoke=True)
            params = T.init_lm(cfg, jax.random.key(0))
            toks = jax.random.randint(jax.random.key(1), (2, 16), 0,
                                      cfg.vocab_size)
            dense_b = B.binarize_tree(params, "det", DEFAULT_POLICY)
            logits_dense, _ = T.forward(cfg, dense_b, toks)
            packed = pack_params(params, DEFAULT_POLICY, "det",
                                 with_scale=False)
            logits_packed, _ = T.forward(cfg, packed, toks)
            np.testing.assert_allclose(
                np.asarray(logits_packed, np.float32),
                np.asarray(logits_dense, np.float32), rtol=5e-2, atol=5e-2)

    def test_packed_leaf_structure(self):
        cfg = cb.get_config("starcoder2_3b", smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        packed = pack_params(params, DEFAULT_POLICY, "det")
        leaf = packed["layers"]["attn"]["w_qkv"]
        assert isinstance(leaf, PackedLinear)
        assert leaf.packed.dtype == jnp.int32
        # stacked layer dim preserved; K packed 32x
        assert leaf.packed.shape[0] == cfg.n_layers
        assert leaf.packed.shape[1] == cfg.d_model // 32
        # embeddings unpacked
        assert not isinstance(packed["embed"]["embedding"], PackedLinear)

    def test_bytes_reduction(self):
        cfg = cb.get_config("starcoder2_3b", smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        packed = pack_params(params, DEFAULT_POLICY, "det", with_scale=False)
        dense, packed_b = packed_param_bytes(packed)
        assert dense / packed_b > 2.0  # smoke model is embedding-heavy

    def test_apply_linear_dispatch(self):
        w = jax.random.normal(jax.random.key(0), (64, 32))
        x = jax.random.normal(jax.random.key(1), (4, 64))
        from repro.kernels import ops
        pl = PackedLinear(ops.binarize_and_pack(w), None, 64)
        got = apply_linear(pl, x)
        want = x @ jnp.where(w > 0, 1.0, -1.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)

    def test_stochastic_packing_reproducible(self):
        cfg = cb.get_config("starcoder2_3b", smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        a = pack_params(params, DEFAULT_POLICY, "stoch", key=jax.random.key(7))
        b = pack_params(params, DEFAULT_POLICY, "stoch", key=jax.random.key(7))
        np.testing.assert_array_equal(
            np.asarray(a["layers"]["attn"]["w_qkv"].packed),
            np.asarray(b["layers"]["attn"]["w_qkv"].packed))


class TestServeEngine:
    def test_greedy_generation_matches_stepwise_forward(self):
        cfg = cb.get_config("starcoder2_3b", smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        engine = ServeEngine(cfg, params)
        prompts = jax.random.randint(jax.random.key(1), (2, 8), 0,
                                     cfg.vocab_size)
        out = engine.generate(prompts, max_new=4)
        assert out.tokens.shape == (2, 4)
        # oracle: greedy via repeated full forward
        seq = prompts
        for i in range(4):
            logits, _ = T.forward(cfg, params, seq)
            nxt = jnp.argmax(logits[:, -1], axis=-1)
            np.testing.assert_array_equal(np.asarray(nxt),
                                          np.asarray(out.tokens[:, i]))
            seq = jnp.concatenate([seq, nxt[:, None]], axis=1)


class TestStepScopes:
    def test_decode_program_names_its_layers(self):
        """The compiled decode step carries the model's named scopes in
        its op metadata: the K/V row writes (scatters into the stacked
        cache) under ``kv_update``, and ``attention``, ``mlp`` and
        ``lm_head`` around the rest. No select over a layer's cache
        remains: the write touches rows, never a whole layer."""
        import re

        cfg = cb.get_config("starcoder2_3b", smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        engine = ServeEngine(cfg, params)
        state = engine.init_decode(2, 8, 4)
        text = engine._decode.lower(params, state.cache,
                                    jnp.zeros((2, 1), jnp.int32)
                                    ).compile().as_text()
        lines = text.splitlines()
        writes = [ln for ln in lines
                  if re.search(r" (scatter|dynamic-update-slice)\(", ln)
                  and "/kv_update/" in ln]
        assert len(writes) >= 2, "K and V cache writes"
        # one layer's cache: (slots, positions, kv heads, head dim)
        layer = ",".join(map(str, state.cache["k"].shape[1:]))
        assert not [ln for ln in lines
                    if re.search(rf"\[{layer}\]\S* select\(", ln)]
        for scope in ("attention", "mlp", "lm_head"):
            assert f"/{scope}/" in text, scope


class TestInPlaceCacheWrite:
    """The model step writes only its own K/V rows into the stacked cache:
    after one decode step and after one prefill chunk, every other row is
    bit-identical to before, and the written rows are the K/V the step
    handed to the cache writer (recorded in an eager run)."""

    FAMILIES = {"dense": ("starcoder2_3b", None),
                "sliding_window": ("h2o_danube_3_4b", 6),
                "hybrid": ("jamba_1_5_large", None)}

    def _setup(self, family):
        import dataclasses

        arch, window = self.FAMILIES[family]
        cfg = cb.get_config(arch, smoke=True)
        if window is not None:
            cfg = dataclasses.replace(cfg, sliding_window=window)
        params = T.init_lm(cfg, jax.random.key(0))
        cache = T.init_cache(cfg, 4, 12)
        ks = jax.random.split(jax.random.key(1), 2)
        for name, key in zip(("k", "v"), ks):   # no zero row hides a write
            cache[name] = jax.random.normal(key, cache[name].shape,
                                            cache[name].dtype)
        return cfg, params, cache

    @staticmethod
    def _spy(monkeypatch):
        """Record (layer index, rows) for every K/V write."""
        from repro.models import attention as A

        seen = []
        rows_fn, chunk_fn = A._write_rows, A._write_chunk

        def rows(cache, r, layer, idx, sh):
            seen.append((int(layer), np.asarray(r)))
            return rows_fn(cache, r, layer, idx, sh)

        def chunk(cfg, cache, r, layer, slot, offset, sh):
            seen.append((int(layer), np.asarray(r)))
            return chunk_fn(cfg, cache, r, layer, slot, offset, sh)

        monkeypatch.setattr(A, "_write_rows", rows)
        monkeypatch.setattr(A, "_write_chunk", chunk)
        return seen

    @staticmethod
    def _expect(cache, seen, at):
        """Old cache with the recorded rows placed at ``at(layer)`` — the
        (slots, rows) index pair each write addresses."""
        want = {n: np.array(cache[n]) for n in ("k", "v")}
        assert len(seen) == 2 * cache["k"].shape[0], "one K and one V " \
            "write per attention layer"
        for i, (layer, rows) in enumerate(seen):
            want["kv"[i % 2]][(layer,) + at(layer)] = rows
        return want

    @pytest.mark.parametrize("family", list(FAMILIES))
    def test_decode_step_writes_one_row_per_slot(self, family, monkeypatch):
        cfg, params, cache = self._setup(family)
        s_cache = cache["k"].shape[2]
        pos = np.array([3, 7, 10, 5], np.int32)    # the ring wraps at 6
        cache["pos"] = jnp.asarray(pos)
        seen = self._spy(monkeypatch)
        tok = jnp.array([[1], [2], [3], [4]], jnp.int32)
        with jax.disable_jit():
            _, new = T.decode_step(cfg, params, dict(cache), tok)
        idx = pos % s_cache if cfg.sliding_window else np.minimum(
            pos, s_cache - 1)
        want = self._expect(cache, seen, lambda l: (np.arange(4), idx))
        for n in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(new[n]), want[n], n)

    @pytest.mark.parametrize("family", list(FAMILIES))
    def test_chunk_writes_its_rows_only(self, family, monkeypatch):
        cfg, params, cache = self._setup(family)
        s_cache = cache["k"].shape[2]
        slot, offset, c = 2, 4, 4          # rows 4..7: past 6, a ring wraps
        seen = self._spy(monkeypatch)
        toks = jnp.arange(1, c + 1, dtype=jnp.int32)[None]
        with jax.disable_jit():
            _, new = T.prefill_chunk(cfg, params, dict(cache), toks,
                                     jnp.int32(slot), jnp.int32(offset))
        rows = (offset + np.arange(c)) % s_cache
        if cfg.sliding_window:
            assert rows.tolist() == [4, 5, 0, 1]
        want = self._expect(cache, seen, lambda l: (slot, rows))
        for n in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(new[n]), want[n], n)
        assert int(new["pos"][slot]) == offset + c


class TestContinuousDecode:
    """Step-level continuous batching: the persistent slot-addressed cache
    must reproduce one-shot generation bit-for-bit."""

    @pytest.mark.parametrize("arch", ["starcoder2_3b", "mamba2_130m",
                                      "jamba_1_5_large"])
    def test_prefill_into_matches_batched_prefill(self, arch):
        """init_decode + per-slot prefill_into builds exactly the cache (and
        first-token logits) a batched prefill would, for every cache family
        (uniform attn / ssm / hybrid)."""
        cfg = cb.get_config(arch, smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        engine = ServeEngine(cfg, params)
        prompts = jax.random.randint(jax.random.key(1), (3, 8), 0,
                                     cfg.vocab_size)
        lg, cache = engine._prefill(params, prompts, 8 + 4)
        state = engine.init_decode(3, 8, 4)
        for s in (2, 0, 1):  # out of order: slot index is data, not shape
            state = engine.prefill_into(state, s, np.asarray(prompts[s]))
        np.testing.assert_array_equal(np.asarray(lg, np.float32),
                                      np.asarray(state.logits, np.float32))
        for k in cache:
            np.testing.assert_array_equal(
                np.asarray(cache[k], np.float32),
                np.asarray(state.cache[k], np.float32), err_msg=k)

    @pytest.mark.parametrize("arch", ["starcoder2_3b", "mamba2_130m"])
    def test_greedy_stream_bit_identical_to_one_shot(self, arch):
        """Greedy streams from the step-level loop == one-shot generate per
        request, through mid-stream slot refill (5 requests, 2 slots) and
        mixed per-request max_new."""
        cfg = cb.get_config(arch, smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        engine = ServeEngine(cfg, params)
        rng = np.random.default_rng(0)
        max_news = [3, 5, 2, 4, 3]
        prompts = [rng.integers(0, cfg.vocab_size, 8) for _ in max_news]
        batcher = SlotBatcher(n_slots=2, prompt_len=8)
        for p, m in zip(prompts, max_news):
            batcher.submit(p, m)
        steps = stream_serve(engine, batcher)
        assert len(batcher.completed) == 5 and batcher.idle
        # this workload packs perfectly onto 2 slots (3+2+4 | 5+3), so the
        # scheduler must hit exactly ceil(sum/slots) emission steps — any
        # wasted or duplicated step breaks the equality
        assert steps == -(-sum(max_news) // 2)
        by_uid = {r.uid: r for r in batcher.completed}
        for uid, (p, m) in enumerate(zip(prompts, max_news)):
            assert len(by_uid[uid].generated) == m
            one = engine.generate(jnp.asarray(p, jnp.int32)[None], m)
            np.testing.assert_array_equal(
                np.asarray(by_uid[uid].generated),
                np.asarray(one.tokens)[0], err_msg=f"request {uid}")

    @pytest.mark.parametrize("arch", ["starcoder2_3b", "mamba2_130m"])
    def test_chunked_stream_bit_identical(self, arch):
        """``decode_chunk > 1`` (the multi-step on-device inner loop) emits
        the same streams AND the same step count as the one-token loop:
        clipping each chunk to ``batcher.min_remaining()`` keeps slot
        turnover on chunk boundaries, so refill timing never diverges."""
        cfg = cb.get_config(arch, smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        engine = ServeEngine(cfg, params)

        def run(chunk):
            rng = np.random.default_rng(0)
            b = SlotBatcher(n_slots=2, prompt_len=8)
            for m in [3, 5, 2, 4, 3]:
                b.submit(rng.integers(0, cfg.vocab_size, 8), m)
            steps = stream_serve(engine, b, decode_chunk=chunk)
            return steps, {r.uid: list(r.generated) for r in b.completed}

        base = run(1)
        for chunk in (3, 64):   # mid-request boundary; chunk > total budget
            assert run(chunk) == base, f"decode_chunk={chunk}"

    def test_chunked_steady_state_has_no_implicit_transfers(self):
        """The whole point of the multi-step inner loop: a steady-state
        chunk crosses the host boundary exactly once, via an *explicit*
        ``jax.device_get`` of the token block. ``jax.transfer_guard
        ("disallow")`` turns any implicit transfer inside the chunk into an
        error, so this fails if a host round-trip sneaks back into the
        decode path (a ``float(...)``, an ``np.asarray`` on logits, a
        non-donated re-placement...)."""
        cfg = cb.get_config("starcoder2_3b", smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        engine = ServeEngine(cfg, params)
        rng = np.random.default_rng(0)
        state = engine.init_decode(2, 8, 8)
        for s in (0, 1):  # prefill outside the guard: prompts are host data
            state = engine.prefill_into(
                state, s, rng.integers(0, cfg.vocab_size, 8))
        with jax.transfer_guard("disallow"):
            state, toks = engine.decode_steps(state, 4)
            chunk = jax.device_get(toks)       # the ONE allowed crossing
        assert chunk.shape == (2, 4)
        # and the chunk really advanced the decode state
        assert int(jax.device_get(state.cache["pos"])[0]) == 8 + 4

    def test_request_timing_ledger(self):
        cfg = cb.get_config("starcoder2_3b", smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        engine = ServeEngine(cfg, params)
        batcher = SlotBatcher(n_slots=2, prompt_len=4)
        rng = np.random.default_rng(0)
        for _ in range(3):
            batcher.submit(rng.integers(0, cfg.vocab_size, 4), 2)
        stream_serve(engine, batcher)
        for r in batcher.completed:
            assert r.ttft is not None and r.ttft >= 0
            assert r.latency is not None and r.latency >= r.ttft

    def test_oversized_max_new_raises(self):
        cfg = cb.get_config("starcoder2_3b", smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        engine = ServeEngine(cfg, params)
        batcher = SlotBatcher(n_slots=1, prompt_len=4)
        batcher.submit(np.arange(4), max_new=9)
        with pytest.raises(ValueError, match="max_new_cap"):
            stream_serve(engine, batcher, max_new_cap=4)


class TestServeCLI:
    def test_packed_cli_serves_without_mesh(self, monkeypatch, capsys):
        """Regression: the primary README serving path (--packed, no
        --mesh) must not forward the compiled plan to ServeEngine —
        plan= without mesh= is a placement error and raises."""
        import sys

        from repro.launch import serve as S

        monkeypatch.setattr(sys, "argv", [
            "serve", "--arch", "starcoder2-3b", "--smoke", "--packed",
            "--requests", "2", "--slots", "2", "--prompt-len", "4",
            "--max-new", "2"])
        S.main()
        out = capsys.readouterr().out
        assert "packed weights" in out
        assert "served 2 requests" in out


class TestServingAccounting:
    def test_tokens_generated_counts_recorded_tokens(self):
        """Regression for the round-loop counter bug: tok/s must come from
        tokens actually recorded — per-request max_new below the cap used
        to be over-credited (mask * global max_new), and slots completing
        within the round were dropped (mask read *after* record)."""
        b = SlotBatcher(n_slots=2, prompt_len=2)
        max_news = [1, 3, 2]
        for i, m in enumerate(max_news):
            b.submit(np.full(2, i), max_new=m)
        cap, legacy_count = 3, 0
        while not b.idle:
            b.refill()
            for _ in range(cap):          # the old round-based recording
                b.record(np.arange(2))
            legacy_count += int(b.active_mask().sum()) * cap
        b.refill()
        assert b.tokens_generated == sum(max_news) == 6
        assert sum(len(r.generated) for r in b.completed) == 6
        # the legacy formula reads the mask after the round completed every
        # slot, so it credits 0 — any steps-times-mask arithmetic is wrong
        assert legacy_count != b.tokens_generated

    def test_tokens_generated_includes_in_flight(self):
        b = SlotBatcher(n_slots=1, prompt_len=2)
        b.submit(np.zeros(2), max_new=4)
        b.refill()
        b.record(np.zeros(1))
        assert b.tokens_generated == 1  # mid-stream, not yet completed


class TestPackedParamBytes:
    def test_dense_baseline_is_true_master_bytes(self):
        """The dense side of the bytes report must equal the bf16 size of
        the *master* tree — K-padded packed layouts (xnor conv's per-tap
        channel padding when C % 32 != 0) must not inflate it."""
        from repro.launch.train import make_paper_policy
        from repro.models import vgg
        tree = vgg.init(jax.random.key(0), width_mult=0.125)
        params = tree["params"]
        assert params["conv"][1]["kernel"].shape[2] % 32 != 0  # K-padded
        packed = pack_params(params, make_paper_policy(len(params["fc"])),
                             "xnor")
        dense_b, packed_b = packed_param_bytes(packed)
        true_dense = sum(leaf.size * 2
                         for leaf in jax.tree_util.tree_leaves(params))
        assert dense_b == true_dense
        assert packed_b < dense_b

    def test_padded_word_layout_reports_master_shape(self):
        """A leaf whose packed array carries extra self-cancelling pad words
        (legal for per-tap layouts) still reports true-K dense bytes."""
        k, n, extra = 64, 8, 3
        packed = jnp.zeros((k // 32 + extra, n), jnp.int32)
        leaf = XnorLinear(packed, None, k)
        assert leaf.master_shape == (k, n)
        dense_b, packed_b = packed_param_bytes({"w": leaf})
        assert dense_b == k * n * 2                 # true master, no pad
        assert packed_b == packed.size * 4          # stored words, with pad

    def test_stacked_master_shape(self):
        pl = PackedLinear(jnp.zeros((5, 2, 64, 7), jnp.int32), None, 64)
        assert pl.master_shape == (5, 2, 64, 7)
        xc = XnorConv(jnp.zeros((9, 4), jnp.int32), None, (3, 3), 20)
        assert xc.master_shape == (3, 3, 20, 4)


class TestTemperedLogprobs:
    def test_logprobs_under_sampled_distribution(self):
        """With temperature > 0, reported logprobs are under the tempered
        softmax(logits / T) the token was drawn from (teacher-forced
        recompute through the full forward pass)."""
        cfg = cb.get_config("starcoder2_3b", smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        engine = ServeEngine(cfg, params)
        prompts = jax.random.randint(jax.random.key(1), (2, 6), 0,
                                     cfg.vocab_size)
        temp = 0.7
        out = engine.generate(prompts, max_new=3, temperature=temp,
                              key=jax.random.key(2))
        seq = prompts
        for i in range(3):
            logits, _ = T.forward(cfg, params, seq)
            lp = jax.nn.log_softmax(
                logits[:, -1].astype(jnp.float32) / temp, axis=-1)
            want = jnp.take_along_axis(lp, out.tokens[:, i][:, None],
                                       axis=-1)[:, 0]
            np.testing.assert_allclose(np.asarray(out.logprobs[:, i]),
                                       np.asarray(want), rtol=2e-3, atol=2e-3)
            seq = jnp.concatenate([seq, out.tokens[:, i][:, None]], axis=1)

    def test_greedy_logprobs_untempered(self):
        cfg = cb.get_config("starcoder2_3b", smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        engine = ServeEngine(cfg, params)
        prompts = jax.random.randint(jax.random.key(1), (1, 6), 0,
                                     cfg.vocab_size)
        out = engine.generate(prompts, max_new=1)
        logits, _ = T.forward(cfg, params, prompts)
        lp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1)
        want = jnp.take_along_axis(lp, out.tokens[:, 0][:, None], axis=-1)[:, 0]
        np.testing.assert_allclose(np.asarray(out.logprobs[:, 0]),
                                   np.asarray(want), rtol=2e-3, atol=2e-3)


class TestSlotBatcher:
    def test_fills_and_completes(self):
        b = SlotBatcher(n_slots=2, prompt_len=4)
        for i in range(5):
            b.submit(np.full(4, i), max_new=3)
        rounds = 0
        while not b.idle:
            b.refill()
            for _ in range(3):
                b.record(np.arange(2))
            rounds += 1
        b.refill()
        assert len(b.completed) == 5
        assert rounds == 3  # ceil(5/2)
        assert all(len(r.generated) == 3 for r in b.completed)

    def test_left_pads_short_prompts(self):
        b = SlotBatcher(n_slots=1, prompt_len=6, pad_id=9)
        b.submit(np.array([1, 2]), max_new=1)
        b.refill()
        np.testing.assert_array_equal(b.prompts()[0],
                                      np.array([9, 9, 9, 9, 1, 2]))
        assert not b.slots[0].truncated

    def test_truncates_long_prompts_to_suffix(self):
        """A prompt longer than the slot width keeps its LAST prompt_len
        tokens (what the next token conditions on), not the first, and the
        request records that it was truncated."""
        b = SlotBatcher(n_slots=1, prompt_len=4)
        b.submit(np.arange(10), max_new=1)
        b.refill()
        np.testing.assert_array_equal(b.prompts()[0], np.array([6, 7, 8, 9]))
        assert b.slots[0].truncated

    def test_refill_retires_and_reuses_slot_in_one_step(self):
        """A slot finishing while the queue is non-empty is retired AND
        refilled by the same refill() call — no idle round in between."""
        b = SlotBatcher(n_slots=2, prompt_len=2)
        for i in range(3):
            b.submit(np.full(2, i), max_new=1)
        b.refill()
        first = [r.uid for r in b.slots]
        for _ in range(1):
            b.record(np.arange(2))  # both slots finish this step
        changed = b.refill()
        # both finished slots retired; slot 0 immediately holds request 2
        assert [r.uid for r in b.completed] == first
        assert changed == [0]
        assert b.slots[0] is not None and b.slots[0].uid == 2
        assert b.slots[1] is None
        assert not b.idle

    def test_all_slots_empty_decodes_masked_padding(self):
        """With every slot empty, the batch decodes pure padding: the mask
        is all-False, prompts are all pad_id, and record() is a no-op."""
        b = SlotBatcher(n_slots=3, prompt_len=4, pad_id=7)
        b.submit(np.arange(4), max_new=1)
        b.refill()
        b.record(np.arange(3))
        b.refill()  # retires the only request; queue empty
        assert b.idle and len(b.completed) == 1
        np.testing.assert_array_equal(b.active_mask(),
                                      np.zeros(3, dtype=bool))
        np.testing.assert_array_equal(b.prompts(),
                                      np.full((3, 4), 7, np.int32))
        b.record(np.arange(3))  # decode output of an all-empty batch
        assert all(r is None for r in b.slots)
        assert len(b.completed[0].generated) == 1  # nothing appended

    def test_prefilling_slots_excluded_from_ledger(self):
        """A slot marked prefilling is occupied (not refilled, not idle)
        but invisible to record / active_mask / min_remaining until
        mark_ready — so its t_first can only ever stamp on a *generated*
        token."""
        b = SlotBatcher(n_slots=2, prompt_len=4)
        b.submit(np.arange(4), max_new=2)
        b.submit(np.arange(4), max_new=5)
        b.refill()
        b.mark_prefilling(1)
        assert b.active_mask().tolist() == [True, False]
        assert b.min_remaining() == 2       # slot 1's budget of 5 ignored
        assert not b.idle
        b.record(np.array([7, 9]))
        assert b.slots[0].generated == [7]
        assert b.slots[1].generated == []   # no decode garbage
        assert b.slots[1].t_first is None
        b.mark_ready(1)
        assert b.active_mask().tolist() == [True, True]
        assert b.min_remaining() == 1
        b.record(np.array([3, 4]))
        assert b.slots[1].generated == [4]
        assert b.slots[1].t_first is not None


def _check_schedule(n_slots, prompt_len, ops):
    """Drive a SlotBatcher through an arbitrary submit/refill/record/
    prefill-toggle schedule and assert the ledger invariants after every
    step: ``tokens_generated`` equals tokens actually recorded, timestamps
    are ordered ``t_submit <= t_first <= t_done``, ``t_done`` implies the
    full ``max_new`` budget, and truncation keeps the prompt SUFFIX."""
    rng = np.random.default_rng(1234)
    b = SlotBatcher(n_slots, prompt_len)
    submitted = {}
    recorded = 0
    for op in ops:
        kind = op[0]
        if kind == "submit":
            plen, max_new = op[1], op[2]
            prompt = rng.integers(0, 100, plen).astype(np.int32)
            uid = b.submit(prompt, max_new)
            submitted[uid] = (prompt, max_new)
        elif kind == "refill":
            b.refill()
        elif kind == "record":
            active = b.active_mask()
            b.record(rng.integers(0, 100, n_slots))
            recorded += int(active.sum())
        elif kind == "prefill_toggle":
            slot = op[1] % n_slots
            if slot in b.prefilling:
                b.mark_ready(slot)
            elif b.slots[slot] is not None and not b.slots[slot].done:
                b.mark_prefilling(slot)
        assert b.tokens_generated == recorded
    b.refill()
    live = [r for r in b.slots if r is not None]
    for r in b.completed + live + list(b.queue):
        prompt, max_new = submitted[r.uid]
        assert len(r.generated) <= max_new
        if r.t_first is not None:
            assert r.t_submit <= r.t_first
        if r.t_done is not None:
            assert r.t_first is not None and r.t_first <= r.t_done
            assert len(r.generated) == max_new
        if len(prompt) >= b.prompt_len:
            np.testing.assert_array_equal(r.prompt,
                                          prompt[-b.prompt_len:])
            assert r.truncated == (len(prompt) > b.prompt_len)
        else:
            np.testing.assert_array_equal(
                r.prompt[b.prompt_len - len(prompt):], prompt)
            assert not r.truncated
            assert (r.prompt[:b.prompt_len - len(prompt)] ==
                    b.pad_id).all()


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:
    # The @given/@settings decorators need hypothesis at class-definition
    # time, so the property class only exists where it is installed; the
    # seeded sweep below exercises the identical checker everywhere.
    class TestSlotBatcherProperties:
        """Property-based ledger invariants: hypothesis explores the
        submit/refill/record/prefill-toggle schedule space."""

        @settings(max_examples=60, deadline=None)
        @given(n_slots=st.integers(1, 4), prompt_len=st.integers(1, 8),
               ops=st.lists(st.one_of(
                   st.tuples(st.just("submit"), st.integers(1, 12),
                             st.integers(1, 6)),
                   st.tuples(st.just("refill")),
                   st.tuples(st.just("record")),
                   st.tuples(st.just("prefill_toggle"),
                             st.integers(0, 7))),
                   max_size=60))
        def test_ledger_invariants(self, n_slots, prompt_len, ops):
            _check_schedule(n_slots, prompt_len, ops)


class TestSlotBatcherRandomSchedules:
    def test_ledger_invariants_random(self):
        """Seeded sweep over 40 random schedules through the same
        invariant checker as the hypothesis properties, so the invariants
        run in tier-1 even where hypothesis is unavailable."""
        rng = np.random.default_rng(7)
        kinds = ["submit", "refill", "record", "record", "prefill_toggle"]
        for _ in range(40):
            n_slots = int(rng.integers(1, 5))
            prompt_len = int(rng.integers(1, 9))
            ops = []
            for _ in range(int(rng.integers(5, 60))):
                k = kinds[int(rng.integers(0, len(kinds)))]
                if k == "submit":
                    ops.append(("submit", int(rng.integers(1, 13)),
                                int(rng.integers(1, 7))))
                elif k == "prefill_toggle":
                    ops.append(("prefill_toggle", int(rng.integers(0, 8))))
                else:
                    ops.append((k,))
            _check_schedule(n_slots, prompt_len, ops)


class TestChunkedPrefillServing:
    def test_ttft_stamps_on_first_generated_token(self):
        """TTFT regression under chunked prefill: with prompts spanning
        three chunks, ``t_first`` must stamp when the first GENERATED
        token lands — never while prefill chunks are completing — and no
        prefill-step garbage may land in the ledger. Streams stay
        bit-identical to one-shot generate."""
        cfg = cb.get_config("starcoder2_3b", smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        engine = ServeEngine(cfg, params)
        prompt_len, chunk = 9, 3  # ceil(9/3) = 3 chunks per prompt
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, cfg.vocab_size, prompt_len)
                   for _ in range(3)]
        b = SlotBatcher(n_slots=2, prompt_len=prompt_len)
        for p in prompts:
            b.submit(p, 4)
        stream_serve(engine, b, max_new_cap=4, prefill_chunk=chunk)
        assert b.idle and len(b.completed) == 3
        for r in b.completed:
            assert len(r.generated) == 4     # exactly max_new, no garbage
            assert r.t_first is not None and r.t_done is not None
            assert r.t_submit <= r.t_first <= r.t_done
            one = engine.generate(
                jnp.asarray(prompts[r.uid], jnp.int32)[None], 4)
            np.testing.assert_array_equal(np.asarray(r.generated),
                                          np.asarray(one.tokens)[0])
        # request 2 waited for a slot: its first token cannot precede the
        # earlier admissions' (prefill chunks never stamp t_first)
        t = {r.uid: r.t_first for r in b.completed}
        assert t[2] >= max(t[0], t[1])

    @staticmethod
    def _admission_ledger(n_requests):
        """Serves ``n_requests`` prompts of 32 tokens in 8-token chunks,
        all queued before the loop starts, and returns the ledger."""
        cfg = cb.get_config("starcoder2_3b", smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        engine = ServeEngine(cfg, params)
        rng = np.random.default_rng(3)
        b = SlotBatcher(n_slots=2, prompt_len=32)
        for _ in range(n_requests):
            b.submit(rng.integers(1, cfg.vocab_size, 32), 3)
        stream_serve(engine, b, max_new_cap=3, prefill_chunk=8)
        assert b.idle and len(b.completed) == n_requests
        return sorted(b.completed, key=lambda r: r.uid)

    def test_admission_ledger_of_a_lone_request(self):
        """A lone prompt of four chunks runs them back to back: admitted
        and ready four loop iterations apart, stamped in order."""
        r, = self._admission_ledger(1)
        assert r.prefill_chunks == 4
        assert r.admit_step == 1
        assert r.ready_step - r.admit_step + 1 == 4
        assert r.t_submit <= r.t_admit <= r.t_first <= r.t_done

    def test_admission_ledger_of_requests_admitted_together(self):
        """Two prompts admitted in one iteration share the one chunk per
        step: the second waits four steps behind the first, so the loop
        spends 12 steps on 8 chunks (1.5 steps per chunk)."""
        a, b = self._admission_ledger(2)
        assert a.admit_step == b.admit_step
        assert a.prefill_chunks == b.prefill_chunks == 4
        assert (a.ready_step - a.admit_step + 1,
                b.ready_step - b.admit_step + 1) == (4, 8)
        steps = sum(r.ready_step - r.admit_step + 1 for r in (a, b))
        assert steps / (a.prefill_chunks + b.prefill_chunks) == 1.5
        assert a.t_admit <= b.t_admit <= b.t_first

    def test_admission_ledger_of_whole_prompt_prefill(self):
        """Without chunking a request's whole prompt goes in as one chunk,
        at the iteration that admits it; a request that waited for a slot
        is admitted later than it was submitted."""
        cfg = cb.get_config("starcoder2_3b", smoke=True)
        params = T.init_lm(cfg, jax.random.key(0))
        engine = ServeEngine(cfg, params)
        b = SlotBatcher(n_slots=1, prompt_len=4)
        for _ in range(2):
            b.submit(np.arange(1, 5), 2)
        stream_serve(engine, b)
        first, second = sorted(b.completed, key=lambda r: r.uid)
        for r in (first, second):
            assert r.prefill_chunks == 1 and r.ready_step == r.admit_step
        assert first.admit_step == 1 and second.admit_step > 1
        assert second.t_admit >= first.t_done
