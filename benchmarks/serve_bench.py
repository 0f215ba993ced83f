"""Serving-loop benchmark: step-level continuous batching vs the legacy
round-based loop.

The paper's inference headline (>9.89x deterministic / >9.91x stochastic
binarized speedup) only matters at serving scale, and sustained *streaming*
throughput — not one-shot batch latency — is where binarized datapaths pay
off (FINN, arXiv:1612.07119; Scaling BNNs, arXiv:1701.03400). This suite
measures:

* step-level continuous batching (``serve.engine.stream_serve``) vs the
  old round-based loop (re-prefill every round, every slot decodes the
  global ``max_new``) at 8 slots under *skewed* per-request ``max_new`` —
  the regime where round barriers waste the most decode steps;
* tokens/s across slot counts (the compiled batch dimension);
* burst vs staggered arrival (requests joining mid-stream through
  ``prefill_into`` — no round barrier to wait for);
* chunked prefill + prefix KV reuse under staggered arrival: whole-prompt
  vs fused ``decode_prefill`` admission (burst-gap ratio + TTFT medians),
  and a shared-prefix workload served cold vs from prefix-cache hits
  (hit TTFT must undercut the cold median);
* dense vs packed vs xnor execution plans under the step-level loop;
* mesh-sharded vs single-device serving (tensor-parallel execution plans
  on a forced 2x2 ("data", "model") CPU mesh, run in a subprocess so this
  process keeps its device count) — on CPU this is a *parity* row (same
  tokens, placement overhead visible), on real multi-chip hardware it is
  the scale-out row.

All throughput numbers divide tokens *actually recorded* by wall time
(``SlotBatcher.tokens_generated``), never steps-times-batch arithmetic.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, save_json

ARCH = "starcoder2_3b"
PROMPT_LEN = 8


def _engine(plan: str):
    from repro.configs import base as cb
    from repro.core.policy import DEFAULT_POLICY
    from repro.models import transformer as T
    from repro.serve.engine import ServeEngine, pack_params

    cfg = cb.get_config(ARCH, smoke=True)
    params = T.init_lm(cfg, jax.random.key(0))
    if plan != "dense":
        params = pack_params(params, DEFAULT_POLICY, plan,
                             key=jax.random.key(1))
    return cfg, ServeEngine(cfg, params)


def _submit_skewed(batcher, cfg, n: int, cap: int, n_long: int, short: int,
                   seed: int = 0):
    """A few cap-length requests + many short ones: the skew that starves a
    round-based loop (every slot decodes the global cap every round)."""
    rng = np.random.default_rng(seed)
    for i in range(n):
        batcher.submit(rng.integers(0, cfg.vocab_size, PROMPT_LEN),
                       cap if i < n_long else short)


def _run_step_loop(engine, batcher, cap: int, metrics=None,
                   chunk: int = 1) -> tuple[float, int, int]:
    from repro.serve.engine import stream_serve

    t0 = time.perf_counter()
    steps = stream_serve(engine, batcher, max_new_cap=cap, metrics=metrics,
                         decode_chunk=chunk)
    return time.perf_counter() - t0, steps, batcher.tokens_generated


def _run_round_loop(engine, batcher, cap: int) -> tuple[float, int, int]:
    """The legacy pre-step-engine loop: every round re-prefills all slots
    and decodes the global cap, with corrected token accounting."""
    t0 = time.perf_counter()
    rounds = 0
    while not batcher.idle:
        batcher.refill()
        result = engine.generate(jnp.asarray(batcher.prompts()), cap)
        for step_tok in np.asarray(result.tokens).T:
            batcher.record(step_tok)
        rounds += 1
    batcher.refill()
    return time.perf_counter() - t0, rounds, batcher.tokens_generated


def _fresh_batcher(cfg, slots: int, prompt_len: int = PROMPT_LEN):
    from repro.serve.batcher import SlotBatcher

    return SlotBatcher(slots, prompt_len)


def _staggered_loop(engine, cfg, slots: int, n: int, cap: int,
                    every: int) -> tuple[float, int, int]:
    """Requests arrive mid-stream (one every ``every`` steps): the hand-
    rolled loop shows the engine primitives absorbing async arrival — a
    new request joins the live batch at the next step, no round barrier."""
    batcher = _fresh_batcher(cfg, slots)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, PROMPT_LEN) for _ in range(n)]
    t0 = time.perf_counter()
    state = engine.init_decode(slots, PROMPT_LEN, cap)
    submitted = steps = 0
    while submitted < n or not batcher.idle:
        while submitted < n and steps >= submitted * every:
            batcher.submit(prompts[submitted], cap)
            submitted += 1
        for slot in batcher.refill():
            state = engine.prefill_into(state, slot, batcher.slots[slot].prompt)
        if batcher.idle:
            if submitted < n:  # queue drained but more arrivals pending
                steps += 1
                continue
            break
        tok = jnp.argmax(state.logits, axis=-1)
        batcher.record(np.asarray(tok))
        steps += 1
        if submitted == n and batcher.idle:
            break              # final emission needs no trailing decode
        state = engine.decode_step(state, tok)
    batcher.refill()
    return time.perf_counter() - t0, steps, batcher.tokens_generated


def _staggered_stream(engine, cfg, slots: int, n: int, cap: int, every: int,
                      *, prefill_chunk: int = 0, prefix_cache=None,
                      shared_prefix: int = 0, prompt_len: int = PROMPT_LEN):
    """Open-loop staggered arrival through ``stream_serve``'s ``arrivals``
    hook (one request every ``every`` iterations; ``every=0`` submits the
    whole batch up front — the burst baseline through the *same* loop
    driver), optionally with chunked prefill, a prefix cache, and a shared
    prompt prefix (the multi-tenant system-prompt workload). Returns the
    batcher for TTFT accounting."""
    from repro.serve.engine import stream_serve

    batcher = _fresh_batcher(cfg, slots, prompt_len)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len) for _ in range(n)]
    if shared_prefix:
        for p in prompts[1:]:
            p[:shared_prefix] = prompts[0][:shared_prefix]
    sub = {"n": 0}

    def arrivals(iteration: int) -> bool:
        while sub["n"] < n and iteration >= sub["n"] * every:
            batcher.submit(prompts[sub["n"]], cap)
            sub["n"] += 1
        return sub["n"] < n

    t0 = time.perf_counter()
    steps = stream_serve(engine, batcher, max_new_cap=cap,
                         prefill_chunk=prefill_chunk,
                         prefix_cache=prefix_cache, arrivals=arrivals)
    return (time.perf_counter() - t0, steps, batcher.tokens_generated,
            batcher)


def _sharded_child(modes: list[str], n: int, cap: int, slots: int,
                   mesh_shape=(2, 2), widen: int = 1,
                   chunk: int = 1) -> dict:
    """Runs inside the forced-multi-device subprocess: serve the same
    workload through a single-device engine and a mesh-sharded engine per
    plan mode; returns tok/s for both (greedy tokens must agree).

    ``widen`` scales d_model / n_heads / d_ff by an integer factor (the
    model-size sweep: where per-device compute grows, the fixed per-step
    collective cost amortizes). Both engines stay *untraced* (the
    ``NULL_TRACER`` default). The returned
    ``manifest`` is this subprocess's own ``run_manifest`` — it, not the
    parent, sees the forced device count and mesh shape."""
    import dataclasses

    from benchmarks.common import run_manifest
    from repro.configs import base as cb
    from repro.core.policy import DEFAULT_POLICY
    from repro.distributed.sharding import make_mesh
    from repro.engine import compile_plan
    from repro.models import transformer as T
    from repro.serve.engine import ServeEngine

    mesh = make_mesh(mesh_shape, ("data", "model"))
    cfg = cb.get_config(ARCH, smoke=True)
    if widen != 1:
        cfg = dataclasses.replace(cfg, d_model=cfg.d_model * widen,
                                  n_heads=cfg.n_heads * widen,
                                  d_ff=cfg.d_ff * widen)
    params = T.init_lm(cfg, jax.random.key(0))
    out = {"manifest": run_manifest(mesh_shape=list(mesh_shape),
                                    widen=widen, decode_chunk=chunk)}
    for mode in modes:
        plan = compile_plan(params, DEFAULT_POLICY, mode, warn=False,
                            mesh=mesh)
        packed = plan.pack(params, key=jax.random.key(1))
        engines = {"single": ServeEngine(cfg, packed),
                   "sharded": ServeEngine(cfg, packed, mesh=mesh, plan=plan)}
        tokens = {}
        for name, eng in engines.items():
            b = _fresh_batcher(cfg, slots)          # warmup/compile
            _submit_skewed(b, cfg, slots, cap, slots, 0)
            _run_step_loop(eng, b, cap, chunk=chunk)
            b = _fresh_batcher(cfg, slots)
            _submit_skewed(b, cfg, n, cap, n, 0)
            dt, steps, toks = _run_step_loop(eng, b, cap, chunk=chunk)
            out[f"{mode}_{name}"] = {"s": dt, "tokens": toks,
                                     "tok_s": toks / dt}
            tokens[name] = {r.uid: list(r.generated) for r in b.completed}
        out[f"{mode}_identical"] = tokens["single"] == tokens["sharded"]
    return out


def _sharded_compare(modes: list[str], n: int, cap: int, slots: int, *,
                     devices: int = 4, mesh_shape=(2, 2), widen: int = 1,
                     chunk: int = 1) -> dict | None:
    """Sharded-vs-single comparison, in a subprocess with ``devices``
    forced host devices (device count is fixed at backend init, so the
    parent process cannot grow one). Returns None if the child fails (e.g.
    no subprocess support on the platform) — the suite keeps going."""
    code = (f"import benchmarks.serve_bench as sb, json; "
            f"print('RESULT ' + json.dumps(sb._sharded_child("
            f"{modes!r}, {n}, {cap}, {slots}, {tuple(mesh_shape)!r}, "
            f"{widen}, {chunk})))")
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), os.pardir, "src"),
         os.path.join(os.path.dirname(__file__), os.pardir),
         env.get("PYTHONPATH", "")])
    try:
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=540)
    except (OSError, subprocess.TimeoutExpired):
        return None
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    print(f"sharded-compare child failed:\n{proc.stderr[-1500:]}",
          file=sys.stderr)
    return None


def main(fast: bool = False):
    slots = 8
    cap = 16 if fast else 32
    short = 2
    n_req = 12 if fast else 24
    n_long = 2 if fast else 4

    record = {}
    rows = []

    # -- step vs round under skewed max_new (the headline comparison) -----
    cfg, engine = _engine("det")
    for loop, runner in (("step", _run_step_loop), ("round", _run_round_loop)):
        b = _fresh_batcher(cfg, slots)       # warmup: compile both paths
        _submit_skewed(b, cfg, slots, cap, 1, short)
        runner(engine, b, cap)
        b = _fresh_batcher(cfg, slots)
        _submit_skewed(b, cfg, n_req, cap, n_long, short)
        if loop == "step":
            # the step loop reports itself through the metrics registry;
            # the artifact keeps the full latency distribution, not just
            # the throughput scalar
            from repro.obs.metrics import MetricsRegistry
            metrics = MetricsRegistry()
            dt, steps, toks = _run_step_loop(engine, b, cap, metrics)
        else:
            metrics = None
            dt, steps, toks = runner(engine, b, cap)
        record[f"{loop}_skewed"] = {"s": dt, "steps": steps, "tokens": toks,
                                    "tok_s": toks / dt}
        if metrics is not None:
            record[f"{loop}_skewed"]["step_latency"] = metrics.histogram(
                "serve_step_seconds").summary()
            record[f"{loop}_skewed"]["ttft"] = metrics.histogram(
                "serve_ttft_seconds").summary()
        rows.append(csv_row(
            f"serve/{loop}_slots{slots}_skewed", dt / max(steps, 1) * 1e6,
            f"tok/s={toks / dt:.1f} tokens={toks}"))
    ratio = record["step_skewed"]["tok_s"] / record["round_skewed"]["tok_s"]
    record["step_over_round"] = ratio
    rows.append(csv_row("serve/step_over_round_skewed", 0.0,
                        f"ratio={ratio:.2f}x (>=1 expected: no round barrier)"))

    # -- slot-count sweep (uniform max_new, step loop) --------------------
    sweep_cap = 8
    for s in ((2, 8) if fast else (2, 4, 8)):
        b = _fresh_batcher(cfg, s)
        _submit_skewed(b, cfg, s, sweep_cap, s, 0)   # warmup this n_slots
        _run_step_loop(engine, b, sweep_cap)
        b = _fresh_batcher(cfg, s)
        _submit_skewed(b, cfg, 2 * s, sweep_cap, 2 * s, 0)
        dt, steps, toks = _run_step_loop(engine, b, sweep_cap)
        record[f"step_slots{s}"] = {"s": dt, "tokens": toks,
                                    "tok_s": toks / dt}
        rows.append(csv_row(f"serve/step_slots{s}_uniform",
                            dt / max(steps, 1) * 1e6,
                            f"tok/s={toks / dt:.1f}"))

    # -- arrival patterns: burst vs staggered (step loop, 4 slots) --------
    arr_slots, arr_n, arr_cap = 4, 8, 8
    b = _fresh_batcher(cfg, arr_slots)               # warmup this n_slots
    _submit_skewed(b, cfg, arr_slots, arr_cap, arr_slots, 0)
    _run_step_loop(engine, b, arr_cap)
    b = _fresh_batcher(cfg, arr_slots)
    _submit_skewed(b, cfg, arr_n, arr_cap, arr_n, 0)
    dt, steps, toks = _run_step_loop(engine, b, arr_cap)
    rows.append(csv_row("serve/arrival_burst", dt / max(steps, 1) * 1e6,
                        f"tok/s={toks / dt:.1f}"))
    record["arrival_burst"] = {"s": dt, "tokens": toks, "tok_s": toks / dt}
    dt, steps, toks = _staggered_loop(engine, cfg, arr_slots, arr_n, arr_cap,
                                      every=2)
    rows.append(csv_row("serve/arrival_staggered", dt / max(steps, 1) * 1e6,
                        f"tok/s={toks / dt:.1f}"))
    record["arrival_staggered"] = {"s": dt, "tokens": toks, "tok_s": toks / dt}

    # -- chunked prefill + prefix KV reuse (staggered arrival) ------------
    # Staggered arrival is where whole-prompt admission hurts: every
    # arriving prompt is a separate prefill dispatch while the live slots
    # wait. Chunked prefill folds admission INTO the decode step (the
    # fused decode_prefill program — one dispatch advances all live slots
    # and one prompt chunk), closing the burst-vs-staggered gap; a prefix
    # cache on a shared-prefix workload then removes the prefill work
    # itself, pulling hit TTFT below the cold median.
    from repro.serve import PrefixCache

    def _ttft_ms(b):
        return float(np.median([r.ttft for r in b.completed]) * 1e3)

    # This section runs on its own geometry: a 16x-longer prompt (the
    # regime the ROADMAP item is about — prefill work comparable to many
    # decode steps; at PROMPT_LEN=8 a whole-prompt prefill costs barely
    # more than one decode step and there is nothing for chunking to
    # hide) and a 32-token cap so admission cost is amortized over a real
    # decode stream. Gap methodology: shared-core CPU drift between runs
    # is +/-15%, larger than the effects measured here, so each row's
    # burst_gap is the MEDIAN over paired samples — every staggered run
    # is immediately preceded by a burst run through the SAME
    # stream_serve driver (``every=0`` = submit everything up front) at
    # the SAME geometry, and the ratio is taken within the pair, where
    # drift cancels. Two chunk sizes: chunk == prompt admits each prompt
    # in ONE fused decode+prefill dispatch; chunk == prompt/4 exercises
    # true multi-chunk admission (and partial prefix snapshots). On this
    # serial-CPU smoke host the fused program's chunk compute cannot
    # overlap decode compute (the compiled fused HLO is op-for-op the sum
    # of decode_step + prefill_chunk), so plain chunked rows carry the
    # admitted slot's masked iterations as visible overhead — the row
    # that closes the burst gap outright is prefix_warm below, where the
    # chunked machinery plus prefix reuse removes the prefill work
    # instead of hiding it. On parallel accelerators, where decode is
    # memory-bound and chunk FLOPs ride along free, the plain chunked
    # rows are the ones expected to close the gap.
    ch_prompt, ch_chunk, ch_cap = 16 * PROMPT_LEN, 4 * PROMPT_LEN, 32
    ch_n, ch_every, ch_pairs = 12, 2, 5

    def _chunk_stream(every: int, **kw):
        return _staggered_stream(engine, cfg, arr_slots, ch_n, ch_cap,
                                 every, prompt_len=ch_prompt, **kw)

    def _paired(pairs: int, **kw):
        """Median-gap estimate: (burst, staggered) sample pairs, ratio
        taken within each pair. Returns the median pair (by gap)."""
        samples = []
        for _ in range(pairs):
            bdt, _bs, btoks, _bb = _chunk_stream(0)
            dt, steps, toks, b = _chunk_stream(ch_every, **kw)
            samples.append(((btoks / bdt) / (toks / dt), dt, steps, toks, b))
        samples.sort(key=lambda s: s[0])
        return samples[len(samples) // 2]

    _chunk_stream(0)                                     # warmup/compile
    chunked = {"prompt_len": ch_prompt, "chunk": ch_chunk, "cap": ch_cap,
               "n": ch_n, "every": ch_every, "pairs": ch_pairs}
    for tag, kw in (("staggered_whole", {}),
                    ("staggered_chunked", {"prefill_chunk": ch_prompt}),
                    ("staggered_chunked_multi",
                     {"prefill_chunk": ch_chunk})):
        _chunk_stream(ch_every, **kw)                    # warmup/compile
        gap, dt, steps, toks, b = _paired(ch_pairs, **kw)
        chunked[tag] = {"s": dt, "tokens": toks, "tok_s": toks / dt,
                        "ttft_ms": _ttft_ms(b), "burst_gap": gap}
        rows.append(csv_row(
            f"serve/{tag}", dt / max(steps, 1) * 1e6,
            f"tok/s={toks / dt:.1f} burst_gap={gap:.2f}x "
            f"ttft_ms={_ttft_ms(b):.1f}"))

    # shared-prefix workload: pass 1 populates the cache (cold, a single
    # unpaired stream), later passes admit every prompt from a
    # full-prompt prefix hit (warm, paired like the rows above — the
    # cache stays warm so the pair loop re-serves it)
    pc = PrefixCache()
    dt, steps, toks, b = _chunk_stream(ch_every, prefill_chunk=ch_chunk,
                                       prefix_cache=pc,
                                       shared_prefix=ch_prompt)
    chunked["prefix_cold"] = {"s": dt, "tok_s": toks / dt,
                              "ttft_ms": _ttft_ms(b)}
    gap, dt, steps, toks, b = _paired(3, prefill_chunk=ch_chunk,
                                      prefix_cache=pc,
                                      shared_prefix=ch_prompt)
    chunked["prefix_warm"] = {"s": dt, "tok_s": toks / dt, "burst_gap": gap,
                              "ttft_ms": _ttft_ms(b), **pc.stats()}
    warm_ttft = chunked["prefix_warm"]["ttft_ms"]
    cold_ttft = chunked["prefix_cold"]["ttft_ms"]
    rows.append(csv_row(
        "serve/staggered_prefix_warm", dt / max(steps, 1) * 1e6,
        f"tok/s={toks / dt:.1f} burst_gap={gap:.2f}x "
        f"ttft_ms={warm_ttft:.1f} (cold {cold_ttft:.1f}) hits={pc.hits} "
        f"skipped={pc.tokens_skipped}tok"))
    record["chunked_prefill"] = chunked

    # -- execution plans under the step loop ------------------------------
    plan_n, plan_cap = (8, 8) if fast else (16, 16)
    for plan in ("dense", "det", "xnor"):
        cfg_p, eng_p = (cfg, engine) if plan == "det" else _engine(plan)
        b = _fresh_batcher(cfg_p, slots)
        _submit_skewed(b, cfg_p, slots, plan_cap, slots, 0)
        _run_step_loop(eng_p, b, plan_cap)
        b = _fresh_batcher(cfg_p, slots)
        _submit_skewed(b, cfg_p, plan_n, plan_cap, plan_n, 0)
        dt, steps, toks = _run_step_loop(eng_p, b, plan_cap)
        record[f"plan_{plan}"] = {"s": dt, "tokens": toks, "tok_s": toks / dt}
        rows.append(csv_row(f"serve/plan_{plan}_slots{slots}",
                            dt / max(steps, 1) * 1e6,
                            f"tok/s={toks / dt:.1f}"))

    # -- mesh-sharded vs single-device (tensor-parallel plans) ------------
    # Two sharded grids, each row a forced-device-count subprocess serving
    # the identical workload through a single-device and a mesh-sharded
    # engine (multi-step decode loop, decode_chunk=4):
    #   * device-scaling curve: 1 / 2 / 4 devices at the base smoke width;
    #   * model-size sweep: 4-device mesh at widen x {d_model, n_heads,
    #     d_ff} — the per-step collective cost is fixed and activation-
    #     sized, so the ratio improves as per-device compute grows.
    # On a shared-core CPU host these are parity rows (every "device" is a
    # timeslice of the same cores, so sharded pays the full collective +
    # partitioning overhead with zero added FLOP throughput); on real
    # multi-chip hardware the same rows are the scale-out claim.
    sh_modes = ["det"] if fast else ["det", "xnor"]
    sh_n, sh_cap, sh_slots = (6, 8, 2) if fast else (8, 16, 4)
    sh_chunk = 4

    def _row(tag, r, mode):
        single = r[f"{mode}_single"]["tok_s"]
        tp = r[f"{mode}_sharded"]["tok_s"]
        rows.append(csv_row(
            f"serve/{tag}_{mode}", 0.0,
            f"single={single:.1f} sharded={tp:.1f} tok/s "
            f"ratio={tp / single:.2f}x identical={r[f'{mode}_identical']}"))
        return tp / single

    ratios = {m: {} for m in sh_modes}
    scaling = {}
    curve = ([(4, (2, 2))] if fast
             else [(1, (1, 1)), (2, (1, 2)), (4, (2, 2))])
    for ndev, shape in curve:
        r = _sharded_compare(sh_modes, sh_n, sh_cap, sh_slots,
                             devices=ndev, mesh_shape=shape, chunk=sh_chunk)
        if r is None:
            continue
        scaling[f"devices{ndev}"] = r
        for mode in sh_modes:
            ratio = _row(f"sharded_devices{ndev}", r, mode)
            if ndev == 4:
                ratios[mode]["widen1"] = ratio
    record["sharded_scaling"] = scaling

    sweep = {}
    for widen in ((2,) if fast else (2, 4)):
        r = _sharded_compare(sh_modes, sh_n, sh_cap, sh_slots, devices=4,
                             mesh_shape=(2, 2), widen=widen, chunk=sh_chunk)
        if r is None:
            continue
        sweep[f"widen{widen}"] = r
        for mode in sh_modes:
            ratios[mode][f"widen{widen}"] = _row(
                f"sharded_widen{widen}", r, mode)
    record["sharded_widen"] = sweep

    # ratio envelope + gate: the best sharded/single ratio per mode rides
    # in the artifact's run_manifest (the envelope CI archives), and a
    # GENEROUS floor turns a catastrophic collective regression (e.g. the
    # decode step re-growing weight-sized gathers) into a red build without
    # flaking on shared-core CI parity physics.
    best = {m: max(v.values()) for m, v in ratios.items() if v}
    record["sharded_ratio"] = ratios
    # promoted from the run_manifest into the results proper: the best
    # sharded/single ratio per mode is the envelope number the README's
    # soft floor (det >= ~0.7, xnor >= ~0.35 on shared-core CPU; hard
    # gate 0.25) tracks across PRs
    record["sharded_ratio_best"] = best
    for mode, r in sorted(best.items()):
        rows.append(csv_row(f"serve/sharded_best_ratio_{mode}", 0.0,
                            f"best_ratio={r:.2f}x (gate: >= 0.25)"))

    save_json("serve_bench", record,
              mesh_shape=[2, 2] if scaling or sweep else None,
              sharded_ratio_best=best or None)
    if best and max(best.values()) < 0.25:
        raise RuntimeError(
            f"sharded/single tok/s best ratio {best} fell below the 0.25 "
            f"floor — the decode step has likely re-grown weight-sized "
            f"collectives (run benchmarks.check_collectives for the diff)")
    return rows


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for line in main():
        print(line)
