"""Serving driver: batched inference with optional packed binary weights.

Demonstrates the paper's inference claim end-to-end: the same model served
with dense master weights vs bitpacked binary weights (+BWN scale), with
per-request TTFT/latency stats and the weight-bytes reduction printed (the
TPU analogue of Table I's inference-time rows). Token archs run *step-level
continuous batching* (``serve.engine.stream_serve``): a persistent
slot-addressed KV cache, per-step slot refill, per-request ``max_new``, and
tok/s derived from tokens actually recorded. The paper's classifiers
(mnist_fc, vgg16_cifar10) run fixed-batch image inference — ``--binarize
xnor`` serves them fully binary (XnorLinear FC + XnorConv blocks 2-5 for
VGG).

Per-layer dispatch is compiled into an explicit execution plan
(``repro.engine``): ``--plan-report`` prints the backend/reason/bytes table,
``--plan out.json`` dumps the manifest (round-trips through
``ExecutionPlan.load``), ``--plan-from in.json`` serves a previously saved
plan, and ``--override path=backend`` forces layers onto a named backend.

Token archs also serve *mesh-sharded*: ``--mesh data,model --mesh-shape
2,4`` places packed weights (out-channel dim TP over "model"), activations
(ShardCtx constraints) and the slot-addressed decode cache (slots over
"data") on an 8-device mesh, per the plan's sharding column. Greedy
streams are bit-identical to single-device serving.

Stochastic *ensemble* serving (``repro.stoch``): ``--ensemble K`` (with
``--packed --binarize stoch``) draws K independent packed replicas of every
stochastic layer, decodes from the ensemble-mean logits, and reports replica
vote agreement / logit variance per request; ``--abstain-threshold`` flags
low-agreement requests. Works for both the token archs (resident replica
cache in the streaming loop) and the classifiers (vmapped batch forward).

Chunked prefill + prefix reuse (single-sample serving): ``--prefill-chunk
C`` admits prompts C tokens at a time through the fused decode+prefill
step — arriving prompts no longer stall live decode slots — and
``--prefix-cache N`` adds an N-entry LRU prompt-prefix KV cache so
requests sharing a prefix (``--shared-prefix P`` on synthetic workloads)
splice cached rows and skip prefill chunks. Streams stay bit-identical to
whole-prompt admission (tests/test_serve_conformance.py).

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b --smoke \
      --packed --requests 16 --prompt-len 32 --max-new 16
  PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b --smoke \
      --packed --prefill-chunk 8 --prefix-cache 32 --shared-prefix 16
  PYTHONPATH=src python -m repro.launch.serve --arch mnist-fc --smoke \
      --packed --binarize stoch --ensemble 8 --abstain-threshold 0.6
  PYTHONPATH=src python -m repro.launch.serve --arch vgg16-cifar10 --smoke \
      --packed --binarize xnor --requests 32 --slots 8 --plan-report
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b --smoke \
      --packed --mesh data,model --mesh-shape 2,2 --requests 8
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import base as cb
from repro.core.policy import DEFAULT_POLICY
from repro.distributed.sharding import make_mesh
from repro.engine import (ExecutionPlan, compile_plan, format_plan_table,
                          plan_report)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.serve.batcher import SlotBatcher
from repro.serve.engine import ServeEngine, packed_param_bytes, stream_serve


def wants_plan(args) -> bool:
    return bool(args.packed or args.plan or args.plan_from
                or args.plan_report or args.override or args.analyze)


def make_serve_mesh(args):
    """Builds the serving mesh from --mesh/--mesh-shape (None when unset).

    ``--mesh data,model`` names the axes; ``--mesh-shape 2,4`` gives the
    per-axis device counts (default: all local devices on the last —
    "model" — axis). On CPU, force a multi-device host with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    if not args.mesh:
        if args.mesh_shape:
            raise SystemExit("--mesh-shape requires --mesh (axis names)")
        return None
    axes = tuple(a.strip() for a in args.mesh.split(",") if a.strip())
    if args.mesh_shape:
        shape = tuple(int(s) for s in args.mesh_shape.split(","))
    else:
        shape = (1,) * (len(axes) - 1) + (jax.device_count(),)
    if len(shape) != len(axes):
        raise SystemExit(f"--mesh has {len(axes)} axes but --mesh-shape "
                         f"has {len(shape)} entries")
    try:
        mesh = make_mesh(shape, axes)
    except (ValueError, AssertionError) as e:
        raise SystemExit(
            f"cannot build mesh {dict(zip(axes, shape))} over "
            f"{jax.device_count()} visible device(s): {e} — on CPU, set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N") from None
    print(f"mesh: {dict(zip(axes, shape))} over {mesh.devices.size} devices")
    return mesh


def mesh_axis_sizes(mesh) -> dict | None:
    """{axis: size} for plan_report's predicted-collective column."""
    if mesh is None:
        return None
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def make_plan(params, policy, args, mesh=None) -> ExecutionPlan:
    """Compile (or load) the execution plan and run the requested plan I/O.

    A loaded plan is authoritative: its recorded mode drives packing and
    the binary-activation forward, superseding ``--binarize``. With a
    ``mesh``, the compiled plan's sharding column is validated against it
    (axes the mesh cannot honour downgrade to replicated)."""
    if (args.plan_from or args.override) and not args.packed:
        raise SystemExit("--plan-from/--override change how weights are "
                         "packed; add --packed (use --plan/--plan-report "
                         "alone for a dry inspection)")
    if args.plan_from:
        if args.override:
            raise SystemExit("--override edits a plan at compile time; it "
                             "cannot be combined with --plan-from")
        plan = ExecutionPlan.load(args.plan_from)
        if plan.mode != args.binarize:
            print(f"plan {args.plan_from} was compiled with mode="
                  f"{plan.mode}; serving that (--binarize {args.binarize} "
                  f"ignored)")
    else:
        overrides = {}
        for kv in args.override:
            if "=" not in kv:
                raise SystemExit(
                    f"--override expects PATH=BACKEND (e.g. "
                    f"conv/3=binarized_dense), got {kv!r}")
            path, backend = kv.split("=", 1)
            overrides[path] = backend
        plan = compile_plan(params, policy, args.binarize,
                            overrides=overrides or None, mesh=mesh,
                            replica_axis=(args.replica_axis
                                          if args.ensemble > 1 else None))
    if args.ensemble > 1 and plan.replica_axis is None:
        # a loaded v2 manifest (or one compiled without ensembles) carries
        # no replica axis; adopt the CLI's
        plan.replica_axis = args.replica_axis
    if args.plan:
        print(f"plan manifest -> {plan.save(args.plan)}")
    if args.plan_report:
        print(format_plan_table(plan_report(
            plan, batch=args.slots, axis_sizes=mesh_axis_sizes(mesh))))
    if not args.packed:
        print("(--packed not set: serving dense master weights; the "
              "compiled plan is not applied)")
    return plan


def serve_classifier(arch: str, args) -> dict:
    """Fixed-batch image-classification serving for the paper's nets.
    Returns the summary :func:`serve` documents."""
    from repro.data import synthetic as syn
    from repro.launch.train import make_paper_policy
    from repro.models import mnist_fc, vgg

    if arch == "mnist_fc":
        from repro.configs import mnist_fc as C
        hidden = C.SMOKE_HIDDEN if args.smoke else C.HIDDEN
        tree = mnist_fc.init(jax.random.key(args.seed), hidden=hidden)
        apply_fn, n_fc, kind = mnist_fc.apply, len(tree["params"]["layers"]), "mnist"
    else:
        from repro.configs import vgg16_cifar10 as C
        wm = C.SMOKE_WIDTH_MULT if args.smoke else C.WIDTH_MULT
        tree = vgg.init(jax.random.key(args.seed), width_mult=wm)
        apply_fn, n_fc, kind = vgg.apply, len(tree["params"]["fc"]), "cifar"

    params, mstate = tree["params"], tree["state"]
    binary_act = False
    ensemble_set = None
    if args.ensemble > 1 and not (args.packed and args.binarize == "stoch"
                                  or args.plan_from):
        raise SystemExit("--ensemble K samples K stochastic replicas: add "
                         "--packed --binarize stoch")
    analysis_findings = None
    if wants_plan(args):
        plan = make_plan(params, make_paper_policy(n_fc), args)
        if args.analyze:
            # classifier serving is fixed-batch single-device: the HLO /
            # retrace layers don't apply, so --analyze is plan lints only
            analysis_findings = plan.lint()
    if args.packed:
        if args.ensemble > 1:
            from repro.stoch import sample_replicas

            if plan.mode != "stoch":
                raise SystemExit(f"--ensemble needs a stochastic plan, got "
                                 f"mode={plan.mode} (--binarize stoch)")
            ensemble_set = sample_replicas(
                params, plan, jax.random.key(args.seed + 1), args.ensemble)
            params = ensemble_set.base
            dense_b, _ = packed_param_bytes(params)
            ens_b = ensemble_set.tree_nbytes()
            print(f"ensemble K={args.ensemble} (stoch): {dense_b/1e6:.1f}MB "
                  f"(bf16 dense, 1 copy) -> {ens_b/1e6:.1f}MB "
                  f"({args.ensemble} packed replicas, shared leaves once)")
        else:
            params = plan.pack(params, key=jax.random.key(args.seed + 1))
            dense_b, packed_b = packed_param_bytes(params)
            print(f"packed weights ({plan.mode}): {dense_b/1e6:.1f}MB (bf16 "
                  f"dense) -> {packed_b/1e6:.1f}MB "
                  f"({dense_b/max(packed_b,1):.1f}x smaller)")
        # the plan's mode (not the CLI flag) decides the sign-activation
        # forward, so a loaded manifest serves self-consistently
        binary_act = plan.mode == "xnor"

    if ensemble_set is not None:
        from repro.stoch import ensemble_forward

        rs = ensemble_set
        fwd = jax.jit(lambda x: ensemble_forward(
            rs, lambda t: apply_fn(t, mstate, x, training=False,
                                   binary_act=binary_act)[0]))
    else:
        fwd = jax.jit(lambda p, s, x: apply_fn(p, s, x, training=False,
                                               binary_act=binary_act)[0])
    metrics = None
    if args.metrics_out:
        from repro.obs import MetricsRegistry

        metrics = MetricsRegistry()
    spec = syn.SyntheticSpec(kind, n_train=max(args.requests, args.slots),
                             batch_size=args.slots, seed=args.seed)
    t0, done, lat = time.perf_counter(), 0, []
    agrees, n_abstained = [], 0
    for step in range(-(-args.requests // args.slots)):
        x, _ = syn.train_batch(spec, step)
        if arch == "mnist_fc":
            x = x.reshape(x.shape[0], -1)
        t1 = time.perf_counter()
        take = min(args.slots, args.requests - done)
        if ensemble_set is not None:
            fwd_args = (x,)
            es = fwd(x)
            logits = es.mean_logits
            preds = jax.numpy.argmax(es.mean_logits, axis=-1)
            jax.block_until_ready(preds)
            agr = np.asarray(es.agreement)[:take]   # drop ragged-batch pad
            agrees.append(agr)
            if args.abstain_threshold is not None:
                n_abstained += int((agr < args.abstain_threshold).sum())
        else:
            fwd_args = (params, mstate, x)
            logits = fwd(params, mstate, x)
            preds = jax.numpy.argmax(logits, axis=-1)
            jax.block_until_ready(preds)
        lat.append(time.perf_counter() - t1)
        done += take
    dt = time.perf_counter() - t0
    print(f"served {done} requests in {len(lat)} batches of {args.slots}, "
          f"{dt:.2f}s ({np.median(lat)*1e3:.1f} ms/batch median, "
          f"{done/dt:.1f} img/s)")
    if agrees:
        alla = np.concatenate(agrees)
        msg = (f"ensemble uncertainty: mean vote agreement {alla.mean():.3f}"
               f" (min {alla.min():.3f})")
        if args.abstain_threshold is not None:
            msg += (f"; abstained {n_abstained}/{done} at threshold "
                    f"{args.abstain_threshold}")
        print(msg)
    if metrics is not None:
        h = metrics.histogram("serve_batch_seconds",
                              "wall seconds per inference batch")
        for s in lat:
            h.observe(s)
        metrics.counter("serve_images_total", "images classified").inc(done)
        metrics.gauge("serve_img_per_s",
                      "images / serving wall seconds").set(done / dt)
        if agrees:
            ah = metrics.histogram(
                "serve_vote_agreement",
                "per-image ensemble replica vote agreement (0-1)")
            for a in np.concatenate(agrees):
                ah.observe(float(a))
            if args.abstain_threshold is not None:
                metrics.counter("serve_abstain_total",
                                "images below the abstain "
                                "threshold").inc(n_abstained)
        if args.metrics_out.endswith((".prom", ".txt")):
            with open(args.metrics_out, "w") as f:
                f.write(metrics.to_prometheus())
            print(f"metrics (prometheus) -> {args.metrics_out}")
        else:
            print(f"metrics -> {metrics.save(args.metrics_out)}")
    if analysis_findings is not None:
        from repro.analysis import format_findings, gate

        print(format_findings(analysis_findings,
                              title="static verifier (plan lints; "
                                    "docs/ANALYSIS.md):"))
        if gate(analysis_findings):
            raise SystemExit(1)
    return {"arch": arch, "plan": plan.mode if args.packed else "dense",
            "requests": done, "seconds": dt, "forward": fwd,
            "forward_args": fwd_args, "logits": logits}


def build_parser() -> argparse.ArgumentParser:
    """The serving CLI's options; ``serve(build_parser().parse_args(argv))``
    runs the same path in-process."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--packed", action="store_true")
    ap.add_argument("--binarize", default="det",
                    choices=["det", "stoch", "xnor"])
    ap.add_argument("--plan", default="",
                    help="dump the compiled execution-plan manifest to this "
                         "JSON path")
    ap.add_argument("--plan-from", default="",
                    help="load (instead of compiling) the execution plan "
                         "from a saved manifest")
    ap.add_argument("--plan-report", action="store_true",
                    help="print the per-layer backend/reason/bytes table")
    ap.add_argument("--override", action="append", default=[],
                    metavar="PATH=BACKEND",
                    help="force a layer (path or '/'-prefix) onto a backend, "
                         "e.g. conv/3=binarized_dense (repeatable)")
    ap.add_argument("--ensemble", type=int, default=1, metavar="K",
                    help="serve a K-replica stochastic ensemble (requires "
                         "--packed --binarize stoch): tokens decode from "
                         "the ensemble-mean logits and every request "
                         "reports vote agreement / logit variance")
    ap.add_argument("--abstain-threshold", type=float, default=None,
                    help="flag a request as abstained when its replica "
                         "vote agreement drops below this (needs "
                         "--ensemble >= 2)")
    ap.add_argument("--replica-axis", default="data",
                    choices=["data", "model"],
                    help="mesh axis the ensemble replica dim shards over "
                         "(recorded in the plan manifest, v3)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16,
                    help="per-request max_new cap (the decode cache is "
                         "sized for prompt_len + max_new positions)")
    ap.add_argument("--max-new-skew", type=int, default=0,
                    help="randomize each request's max_new down by up to "
                         "this many tokens (exercises per-step slot refill)")
    ap.add_argument("--prefill-chunk", type=int, default=0, metavar="C",
                    help="admit prompts C tokens at a time through the "
                         "fused decode+prefill step instead of stalling "
                         "every live slot on a whole-prompt prefill "
                         "(0 = whole-prompt; token archs, single-sample "
                         "serving only)")
    ap.add_argument("--prefix-cache", type=int, default=0, metavar="N",
                    help="enable the prompt-prefix KV cache with an N-entry "
                         "LRU budget (0 = off): requests sharing a prompt "
                         "prefix splice the cached rows and skip those "
                         "prefill chunks; implies chunked admission")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="P",
                    help="give every generated request the same first P "
                         "prompt tokens (demonstrates --prefix-cache hits "
                         "on synthetic workloads)")
    ap.add_argument("--mesh", default="",
                    help="serve tensor-parallel on a device mesh: comma-"
                         "separated axis names, e.g. 'data,model' (token "
                         "archs only)")
    ap.add_argument("--mesh-shape", default="",
                    help="per-axis device counts for --mesh, e.g. '2,4' "
                         "(default: all devices on the last axis)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default="", metavar="OUT.json",
                    help="record a Chrome trace of the serving loop "
                         "(span per step: refill/prefill/sample/"
                         "token_sync/record/decode enqueue) and write it "
                         "here — open in Perfetto; token archs only")
    ap.add_argument("--metrics-out", default="", metavar="OUT.json",
                    help="write serving metrics (tok/s, TTFT, per-step "
                         "latency p50/p95/p99, queue depth, slot "
                         "occupancy, ensemble agreement/abstains) here; "
                         "a .prom/.txt suffix selects Prometheus text "
                         "exposition instead of JSON")
    ap.add_argument("--audit-collectives", action="store_true",
                    help="print the static per-step collective audit of "
                         "the jitted decode_step/prefill_into (exact "
                         "count + operand bytes per collective kind, "
                         "from the compiled HLO; token archs only)")
    ap.add_argument("--analyze", action="store_true",
                    help="run the static verifier (repro.analysis): plan "
                         "lints over the compiled plan, compiled-HLO "
                         "lints (donation/upcasts/host transfers; token "
                         "archs), and the retrace sentinel over the "
                         "serving loop — exits nonzero on error findings "
                         "(docs/ANALYSIS.md)")
    return ap


def serve(args) -> dict:
    """Serves ``args`` (from :func:`build_parser`) and returns a summary:
    ``arch``, ``plan`` (the packed plan's mode, or "dense"), ``requests``
    completed and wall ``seconds``. Token archs add ``tokens``, ``steps``,
    the ``engine``, the ``completed`` requests and the ``prefix_cache``
    stats (None without ``--prefix-cache``); classifiers add the
    jitted ``forward``, the ``forward_args`` of its last batch and that
    batch's ``logits``."""
    arch = cb.canonical_arch(args.arch)
    if (args.prefill_chunk or args.prefix_cache) and args.ensemble > 1:
        raise SystemExit("--prefill-chunk/--prefix-cache are single-sample "
                         "serving features; K-replica ensemble serving "
                         "prefills whole prompts")
    if arch in ("mnist_fc", "vgg16_cifar10"):
        if args.mesh:
            raise SystemExit("--mesh serving covers the token archs; the "
                             "classifier path is fixed-batch single-device")
        if args.prefill_chunk or args.prefix_cache:
            raise SystemExit("--prefill-chunk/--prefix-cache chunk the "
                             "token-arch prompt admission; the classifier "
                             "path has no prompts")
        if args.trace or args.audit_collectives:
            raise SystemExit("--trace/--audit-collectives instrument the "
                             "step-level token serving loop; the classifier "
                             "path is fixed-batch (use --metrics-out)")
        return serve_classifier(arch, args)
    cfg = cb.get_config(arch, smoke=args.smoke)
    if cfg.frontend:
        raise SystemExit(f"{arch} uses a stubbed frontend; serve a token arch")
    mesh = make_serve_mesh(args)
    # weights are held in the serving dtype, never as float32 masters
    params = T.init_lm(cfg, jax.random.key(args.seed),
                       dtype=cfg.activation_dtype)
    plan = None
    ensemble_set = None
    if args.ensemble > 1 and not (args.packed and args.binarize == "stoch"
                                  or args.plan_from):
        raise SystemExit("--ensemble K samples K stochastic replicas: add "
                         "--packed --binarize stoch")
    if wants_plan(args):
        plan = make_plan(params, DEFAULT_POLICY, args, mesh=mesh)
    if args.packed:
        if args.ensemble > 1:
            from repro.stoch import sample_replicas

            if plan.mode != "stoch":
                raise SystemExit(f"--ensemble needs a stochastic plan, got "
                                 f"mode={plan.mode} (--binarize stoch)")
            # same key the single-sample pack uses, so replica 0 — and the
            # whole K=1 ensemble — is bit-identical to --packed alone
            ensemble_set = sample_replicas(
                params, plan, jax.random.key(args.seed + 1), args.ensemble)
            params = ensemble_set.base
            dense_b, _ = packed_param_bytes(params)
            ens_b = ensemble_set.tree_nbytes()
            print(f"ensemble K={args.ensemble} (stoch): {dense_b/1e6:.1f}MB "
                  f"(bf16 dense, 1 copy) -> {ens_b/1e6:.1f}MB "
                  f"({args.ensemble} packed replicas, shared leaves once)")
        else:
            params = plan.pack(params, key=jax.random.key(args.seed + 1))
            dense_b, packed_b = packed_param_bytes(params)
            print(f"packed weights ({plan.mode}): {dense_b/1e6:.1f}MB (bf16 "
                  f"dense) -> {packed_b/1e6:.1f}MB "
                  f"({dense_b/max(packed_b,1):.1f}x smaller)")

    # mesh=None serves single-device; with a mesh the engine places the
    # (packed) tree per the plan's sharding column and shards decode slots
    # over "data" — greedy streams stay bit-identical either way. The plan
    # is placement input only, so it is forwarded only alongside a mesh.
    tracer = None
    if args.trace:
        from repro.obs import Tracer

        tracer = Tracer()
    metrics = None
    if args.metrics_out:
        from repro.obs import MetricsRegistry

        metrics = MetricsRegistry()
    engine = ServeEngine(
        cfg, None if ensemble_set is not None else params, mesh=mesh,
        plan=plan if (args.packed and mesh is not None) else None,
        ensemble=ensemble_set, abstain_threshold=args.abstain_threshold,
        tracer=tracer)
    if args.audit_collectives:
        from repro.obs import audit_engine, format_audit

        print("static per-step collective audit (compiled HLO, "
              "trip-count weighted):")
        print(format_audit(audit_engine(
            engine, n_slots=args.slots, prompt_len=args.prompt_len,
            max_new_cap=args.max_new)))
    findings, sentinel = [], None
    if args.analyze:
        from repro.analysis import RetraceSentinel, lint_engine

        findings += plan.lint(
            mesh_axes=mesh.axis_names if mesh is not None else None,
            axis_sizes=mesh_axis_sizes(mesh))
        findings += lint_engine(engine, n_slots=args.slots,
                                prompt_len=args.prompt_len,
                                max_new_cap=args.max_new)
        sentinel = RetraceSentinel(engine)
    prefix_cache = None
    if args.prefix_cache:
        from repro.serve import PrefixCache

        prefix_cache = PrefixCache(max_entries=args.prefix_cache)
    batcher = SlotBatcher(args.slots, args.prompt_len, tracer=tracer)
    rng = np.random.default_rng(args.seed)
    shared = (rng.integers(0, cfg.vocab_size,
                           min(args.shared_prefix, args.prompt_len))
              if args.shared_prefix else None)
    for i in range(args.requests):
        # per-request max_new: uniform in [max(1, max_new - skew), max_new]
        m = args.max_new - int(rng.integers(0, args.max_new_skew + 1))
        prompt = rng.integers(0, cfg.vocab_size, args.prompt_len)
        if shared is not None:
            prompt[:shared.shape[0]] = shared
        batcher.submit(prompt, max(1, m))

    t0 = time.perf_counter()
    steps = stream_serve(engine, batcher, max_new_cap=args.max_new,
                         metrics=metrics, sentinel=sentinel,
                         prefill_chunk=args.prefill_chunk,
                         prefix_cache=prefix_cache)
    dt = time.perf_counter() - t0
    done = batcher.completed
    # throughput from tokens actually recorded — never steps * batch, which
    # over-credits requests whose max_new is below the cap
    n_tokens = batcher.tokens_generated
    ttft = np.median([r.ttft for r in done]) if done else float("nan")
    lat = np.median([r.latency for r in done]) if done else float("nan")
    print(f"served {len(done)} requests in {steps} decode steps, {dt:.2f}s "
          f"({n_tokens} tokens, {n_tokens/dt:.1f} tok/s; median TTFT "
          f"{ttft*1e3:.1f} ms, median latency {lat*1e3:.1f} ms)")
    if prefix_cache is not None:
        s = prefix_cache.stats()
        print(f"prefix cache: {s['hits']} hits / {s['misses']} misses, "
              f"{s['tokens_skipped']} prompt tokens skipped, "
              f"{s['entries']} entries ({s['bytes']/1e6:.1f}MB), "
              f"{s['evictions']} evictions")
    if ensemble_set is not None and done:
        alla = np.array([a for r in done for a in r.agreement])
        n_abst = sum(1 for r in done if r.abstained)
        msg = (f"ensemble uncertainty: mean vote agreement "
               f"{alla.mean():.3f} (min {alla.min():.3f})")
        if args.abstain_threshold is not None:
            msg += (f"; abstained {n_abst}/{len(done)} requests at "
                    f"threshold {args.abstain_threshold}")
        print(msg)
    if metrics is not None:
        h = metrics["serve_step_seconds"].summary()
        if h.get("count"):
            print(f"step latency: p50 {h['p50'] * 1e3:.1f} ms, p95 "
                  f"{h['p95'] * 1e3:.1f} ms, p99 {h['p99'] * 1e3:.1f} ms "
                  f"over {h['count']} steps")
        if args.metrics_out.endswith((".prom", ".txt")):
            with open(args.metrics_out, "w") as f:
                f.write(metrics.to_prometheus())
            print(f"metrics (prometheus) -> {args.metrics_out}")
        else:
            print(f"metrics -> {metrics.save(args.metrics_out)}")
    if tracer is not None:
        from repro.obs import validate_trace

        path = tracer.save(args.trace)
        info = validate_trace(path)
        cov = ("n/a" if info["coverage"] is None
               else f"{info['coverage'] * 100:.1f}%")
        print(f"trace -> {path} ({info['spans']} spans, step coverage "
              f"{cov}; open in https://ui.perfetto.dev)")
    if args.analyze:
        from repro.analysis import format_findings, gate

        findings += sentinel.findings()
        print(sentinel.summary())
        print(format_findings(findings, title="static verifier "
                                              "(docs/ANALYSIS.md):"))
        if gate(findings):
            raise SystemExit(1)
    return {"arch": arch, "plan": plan.mode if args.packed else "dense",
            "requests": len(done), "tokens": n_tokens, "steps": steps,
            "seconds": dt, "engine": engine, "completed": done,
            "prefix_cache": (prefix_cache.stats()
                             if prefix_cache is not None else None)}


def main(argv=None) -> None:
    enable_compile_cache()
    serve(build_parser().parse_args(argv))


if __name__ == "__main__":
    main()
