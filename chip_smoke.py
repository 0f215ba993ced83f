"""Smoke run of the serving path on a TPU, through the serving CLI's own code.

One process, in order:

1. kernel parity at the main path's shapes: ``xnor_matmul``, ``sign_pack``,
   ``patch_pack`` and ``binarize_pack`` (det, and stoch with given random
   words) must equal their ``ref.py`` oracles exactly; ``binary_matmul``
   must match ``binary_matmul_ref`` within a summation-order bound;
2. starcoder2-3b at its published config (30 layers, d_model 3072, bf16,
   random weights from ``--seed``) served by ``repro.launch.serve.serve``
   under the dense, det and xnor plans, the xnor plan through chunked
   prefill with the prefix cache; one det request's stream must equal
   ``ServeEngine.generate``;
3. vgg16_cifar10 at width 1.0 under the xnor plan, and mnist_fc at
   784-2048x3-10 as a 4-replica stochastic ensemble.

Each phase prints one line: requests and tokens served, seconds spent in
the XLA backend compiler, the ``tpu_custom_call`` count of the compiled
decode step (the forward, for the classifiers), and device memory. Times
printed are smoke timings, not metrics. The det and xnor decode steps must
hold Pallas kernels, so a wrapper that quietly took its jnp reference fails
the run.

``--four-chips`` runs only this instead: starcoder2-3b det and xnor on a
2x2 ("data", "model") mesh against the single-device engine on the same
prompts, whose greedy streams must be bit-identical, with per-device bytes
in use printed after the mesh engine is placed.

The last line of a run that passed is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any failure, including finding no TPU, exits non-zero before it.

  python chip_smoke.py [--seed N] [--four-chips]
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

GB = 1e9
SLOTS, PROMPT_LEN, MAX_NEW = 4, 128, 16
LM = ["--arch", "starcoder2_3b", "--requests", "8", "--slots", str(SLOTS),
      "--prompt-len", str(PROMPT_LEN), "--max-new", str(MAX_NEW)]
LM_PHASES = {
    "dense": LM,
    "det": LM + ["--packed", "--binarize", "det"],
    "xnor": LM + ["--packed", "--binarize", "xnor", "--prefill-chunk", "32",
                  "--prefix-cache", "8", "--shared-prefix", "64"],
}
CLASSIFIER_PHASES = {
    "vgg16_cifar10 xnor": ["--arch", "vgg16_cifar10", "--packed",
                           "--binarize", "xnor", "--requests", "16",
                           "--slots", "8"],
    "mnist_fc stoch x4": ["--arch", "mnist_fc", "--packed", "--binarize",
                          "stoch", "--ensemble", "4", "--requests", "64",
                          "--slots", "16"],
}


class CompileClock:
    """Seconds the XLA backend compiler has run in this process."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration


def release() -> None:
    """Frees the last phase's device arrays now: an engine's jitted
    closures refer back to it, so only the cycle collector frees it, and
    the next phase needs the HBM."""
    gc.collect()


def check(cond, msg):
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def memory_line() -> str:
    st = jax.devices()[0].memory_stats() or {}
    return (f"peak {st.get('peak_bytes_in_use', 0) / GB:.2f} GB, "
            f"in use {st.get('bytes_in_use', 0) / GB:.2f} GB")


# ---------------------------------------------------------------------------
# 1. kernel parity
# ---------------------------------------------------------------------------

def kernel_parity(seed: int, interpret: bool = False) -> None:
    """Runs every Pallas kernel of the main path at its shapes and compares
    it with its jnp oracle."""
    from repro.kernels import ref as kref
    from repro.kernels.binary_matmul import binary_matmul_pallas
    from repro.kernels.ops import binarize_and_pack
    from repro.kernels.stoch_binarize import binarize_pack_pallas
    from repro.xnor import ref as xref
    from repro.xnor.conv import ref as cref
    from repro.xnor.conv.kernel import patch_pack_pallas
    from repro.xnor.kernel import lane_words, sign_pack_rows, xnor_matmul_pallas

    base, count = jax.random.key(seed), iter(range(1 << 20))
    fresh = lambda: jax.random.fold_in(base, next(count))  # noqa: E731
    normal = lambda shape, dt=jnp.bfloat16: jax.random.normal(  # noqa: E731
        fresh(), shape, jnp.float32).astype(dt)

    def exact(name, got, want):
        got, want = np.asarray(got), np.asarray(want)
        check(got.shape == want.shape and np.array_equal(got, want),
              f"{name}: kernel != oracle "
              f"({int((got != want).sum())} of {want.size} differ)")
        print(f"parity {name}: exact over {want.size} values", flush=True)

    # (K, N) of the starcoder2-3b projections: qkv, up, down
    for m in (8, 128):                                     # decode, prefill M
        bmm = jax.jit(lambda x, w, s: binary_matmul_pallas(
            x, w, s, block_m=m, interpret=interpret))
        for kdim, n in ((3072, 3584), (3072, 12288), (12288, 3072)):
            name = f"binary_matmul m={m} k={kdim} n={n}"
            wp = binarize_and_pack(normal((kdim, n)))
            scale = jax.random.uniform(fresh(), (n,), minval=0.5, maxval=2.0)
            # Small integer activations (|x| < 2**8, exact in bf16): every
            # product and partial sum is an integer below 2**24, exact in
            # f32 in any order, so the kernel must equal the oracle exactly.
            x = jnp.clip(jnp.round(normal((m, kdim), jnp.float32) * 3),
                         -255, 255).astype(jnp.bfloat16)
            exact(name + " integer x", bmm(x, wp, scale),
                  kref.binary_matmul_ref(x, wp, scale,
                                         compute_dtype=jnp.bfloat16))
            # Real activations: both sides sum the same exact f32 products
            # (bf16 x ±1 is exact), the kernel per 512-row K block and XLA
            # in its own tiling. Any order is within (K-1) * 2**-24 * sum|x|
            # of the exact sum, so the two are within twice that, plus one
            # rounding each for the scale multiply: 2K * 2**-24 * sum|x|.
            x = normal((m, kdim))
            err = np.abs(np.asarray(bmm(x, wp, scale)) - np.asarray(
                kref.binary_matmul_ref(x, wp, scale,
                                       compute_dtype=jnp.bfloat16)))
            x_abs = np.abs(np.asarray(x, np.float32)).sum(axis=1)[:, None]
            bound = 2 * kdim * 2.0 ** -24 * x_abs * np.asarray(scale)
            check(bool((err <= bound).all()),
                  f"{name}: max error {err.max():.3g} above its "
                  f"summation bound")
            print(f"parity {name}: max error {err.max():.3g} within the "
                  f"summation bound (max {bound.max():.3g})", flush=True)

    for m in (8, 128):
        for kdim, n in ((3072, 3584), (12288, 3072)):
            x = normal((m, kdim))
            a = jax.jit(lambda x: sign_pack_rows(x, interpret=interpret))(x)
            exact(f"sign_pack m={m} k={kdim}", a, xref.sign_pack_ref(x))
            wp = binarize_and_pack(normal((kdim, n)))
            bk32 = lane_words(kdim // 32, 16)
            scale = jax.random.uniform(fresh(), (n,), minval=0.5,
                                       maxval=2.0)
            for s in (None, scale):
                got = jax.jit(lambda a, w, s: xnor_matmul_pallas(
                    a, w, s, k_total=kdim, block_m=m, block_k=32 * bk32,
                    interpret=interpret))(a, wp, s)
                exact(f"xnor_matmul m={m} k={kdim} n={n}"
                      f"{'' if s is None else ' scaled'}", got,
                      xref.xnor_matmul_ref(a, wp, kdim, s))

    w = normal((3072, 12288))
    got = jax.jit(lambda w: binarize_pack_pallas(
        w, stochastic=False, interpret=interpret))(w)
    exact("binarize_pack det k=3072 n=12288", got,
          kref.det_binarize_pack_ref(w))
    bits = jax.random.bits(fresh(), w.shape, jnp.uint32)
    got = jax.jit(lambda w, b: binarize_pack_pallas(
        w, b, stochastic=True, interpret=interpret))(w, bits)
    exact("binarize_pack stoch k=3072 n=12288", got,
          kref.stoch_binarize_pack_ref(w, bits))

    # VGG's widest blocks: 512 channels at 4x4 and 2x2, SAME 3x3
    for hw in (4, 2):
        x = normal((8, hw, hw, 512), jnp.float32)
        xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
        got = jax.jit(lambda xp: patch_pack_pallas(
            xp, ksize=(3, 3), oh=hw, ow=hw, interpret=interpret))(xp)
        exact(f"patch_pack {hw}x{hw}x512", got,
              cref.sign_pack_patches_ref(x, (3, 3)))


# ---------------------------------------------------------------------------
# 2-3. serving phases
# ---------------------------------------------------------------------------

def serve_phase(name: str, argv: list, clock: CompileClock) -> dict:
    """Serves ``argv`` through ``repro.launch.serve.serve`` and prints the
    phase line. Returns the serve summary plus ``custom_calls``."""
    from repro.launch.serve import build_parser, serve

    compiled0, t0 = clock.seconds, time.perf_counter()
    summary = serve(build_parser().parse_args(argv))
    wall = time.perf_counter() - t0
    if "engine" in summary:
        eng = summary["engine"]
        state = eng.init_decode(SLOTS, PROMPT_LEN, MAX_NEW)
        with eng._mesh_ctx():
            lowered = eng._decode.lower(eng.params, state.cache,
                                        jnp.zeros((SLOTS, 1), jnp.int32))
        what = "decode step"
    else:
        lowered = summary["forward"].lower(*summary["forward_args"])
        what = "forward"
    summary["custom_calls"] = lowered.compile().as_text().count(
        "tpu_custom_call")
    tokens = summary.get("tokens")
    print(f"phase {name}: plan {summary['plan']}, {summary['requests']} "
          f"requests"
          + (f", {tokens} tokens in {summary['steps']} steps"
             if tokens is not None else "")
          + f"; compile {clock.seconds - compiled0:.1f} s; "
          f"{summary['custom_calls']} tpu_custom_call in the compiled "
          f"{what}; {memory_line()}; smoke wall {wall:.1f} s", flush=True)
    return summary


def check_lm(summary: dict, name: str) -> None:
    from repro.configs import base as cb

    vocab = cb.get_config("starcoder2_3b").vocab_size
    done = summary["completed"]
    check(len(done) == 8, f"{name}: {len(done)} of 8 requests completed")
    for r in done:
        check(len(r.generated) == MAX_NEW
              and all(0 <= t < vocab for t in r.generated),
              f"{name}: request {r.uid} streamed {r.generated}")


def check_classifier(summary: dict, name: str, n_classes: int = 10) -> None:
    logits = np.asarray(summary["logits"], np.float32)
    check(logits.ndim == 2 and logits.shape[1] == n_classes
          and np.isfinite(logits).all(),
          f"{name}: logits {logits.shape} not finite (batch, {n_classes})")


def check_stream_is_generate(summary: dict) -> None:
    """One request's stream_serve tokens == ServeEngine.generate's."""
    req = summary["completed"][0]
    want = summary["engine"].generate(
        jnp.asarray(req.prompt, jnp.int32)[None], MAX_NEW).tokens
    want = np.asarray(want)[0].tolist()
    check(want == list(req.generated),
          f"stream {req.generated} != generate {want}")
    print(f"request {req.uid}: stream_serve tokens == ServeEngine.generate "
          f"tokens", flush=True)


def one_chip(seed: int, clock: CompileClock) -> None:
    kernel_parity(seed)
    seed_args = ["--seed", str(seed)]
    for plan, argv in LM_PHASES.items():
        summary = serve_phase(f"starcoder2-3b {plan}", argv + seed_args,
                              clock)
        check_lm(summary, plan)
        if plan != "dense":
            check(summary["custom_calls"] > 0,
                  f"{plan} decode step holds no Pallas kernel")
        if plan == "det":
            check_stream_is_generate(summary)
        if plan == "xnor":
            pc = summary["prefix_cache"]
            check(pc["hits"] > 0, f"xnor prefix cache never hit: {pc}")
        del summary
        release()
    for name, argv in CLASSIFIER_PHASES.items():
        summary = serve_phase(name, argv + seed_args, clock)
        check_classifier(summary, name)
        del summary
        release()


def four_chips(seed: int, clock: CompileClock) -> None:
    check(len(jax.devices()) == 4, f"--four-chips needs 4 devices, found "
                                   f"{len(jax.devices())}")
    mesh_args = ["--mesh", "data,model", "--mesh-shape", "2,2"]
    mismatches = []
    for plan in ("det", "xnor"):
        argv = LM + ["--packed", "--binarize", plan, "--seed", str(seed)]
        summary = serve_phase(f"starcoder2-3b {plan} 2x2 mesh",
                              argv + mesh_args, clock)
        check_lm(summary, plan)
        check(summary["custom_calls"] > 0,
              f"{plan} decode step on the mesh holds no Pallas kernel")
        in_use = [d.memory_stats()["bytes_in_use"] / GB
                  for d in jax.devices()]
        print(f"{plan} on the mesh: bytes in use per device "
              + ", ".join(f"{b:.2f} GB" for b in in_use), flush=True)
        sharded = {r.uid: r.generated for r in summary["completed"]}
        del summary
        release()
        summary = serve_phase(f"starcoder2-3b {plan} one device", argv,
                              clock)
        single = {r.uid: r.generated for r in summary["completed"]}
        del summary
        release()
        differ = {uid: next(i for i, (a, b) in enumerate(
                      zip(sharded[uid] + [None], single[uid] + [None]))
                      if a != b)
                  for uid in single if sharded.get(uid) != single[uid]}
        if differ:
            mismatches.append(f"{plan} (request: first differing token "
                              f"{differ})")
        print(f"{plan}: greedy streams on the 2x2 mesh "
              f"{'!=' if differ else '=='} one device "
              f"({len(differ)} of {len(single)} requests differ)", flush=True)
    check(not mismatches, "2x2 streams differ from one device: "
          + "; ".join(mismatches))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="only the 2x2-mesh vs one-device stream check")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    check(dev.platform == "tpu", f"no TPU found (platform {dev.platform})")

    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    if args.four_chips:
        four_chips(args.seed, clock)
    else:
        one_chip(args.seed, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
