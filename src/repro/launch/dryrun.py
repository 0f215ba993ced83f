"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

THE proof of distribution coherence without hardware: for each assigned
architecture and input shape, the jitted ``train_step`` / ``serve_step`` is
lowered with ShapeDtypeStruct inputs against the production mesh (16x16
single-pod, 2x16x16 multi-pod), compiled ahead-of-time, and analyzed:

  * ``compiled.memory_analysis()``  — proves the cell fits per-device HBM,
  * ``compiled.cost_analysis()``    — XLA's own FLOPs/bytes (recorded as a
    cross-check; it undercounts scan bodies on the CPU backend),
  * ``core.hlo_analysis.analyze``   — trip-count-aware FLOPs / memory /
    collective bytes, the inputs to the §Roofline terms.

Results are cached as one JSON per cell under ``--out`` so the 80+ cells can
be (re)run incrementally; ``benchmarks/roofline_report.py`` renders the
table in EXPERIMENTS.md from them.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --binarize det --out benchmarks/results/dryrun
"""
# The 512 placeholder devices MUST be configured before any jax import.
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import base as cb                    # noqa: E402
from repro.core import hlo_analysis as H                # noqa: E402
from repro.core import roofline as R                    # noqa: E402
from repro.core.policy import DEFAULT_POLICY            # noqa: E402
from repro.distributed.sharding import ShardCtx, mesh_context, params_pspecs  # noqa: E402
from repro.launch import specs as SP                    # noqa: E402
from repro.launch.mesh import make_production_mesh      # noqa: E402
from repro.models import transformer as T               # noqa: E402
from repro.optim import schedules                       # noqa: E402
from repro.optim.sgd import sgd_momentum                # noqa: E402
from repro.train import steps as ST                     # noqa: E402

TRAIN_FSDP_THRESHOLD = 5e9     # f32 master + momentum on 16 GiB chips
SERVE_FSDP_THRESHOLD = 40e9    # bf16 params at TP=16 on 16 GiB chips


def _ns(mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def _train_model_flops(cfg, shape):
    return R.model_flops_train(cfg.param_count(active_only=True),
                               shape.global_batch * shape.seq_len)


def _serve_model_flops(cfg, shape, kind):
    n_tok = shape.global_batch * (shape.seq_len if kind == "prefill" else 1)
    return R.model_flops_infer(cfg.param_count(active_only=True), n_tok)


def lower_train(cfg, shape, mesh, binarize_mode, mu_bf16: bool = False):
    sh = ShardCtx(mesh)
    fsdp = cfg.param_count() > TRAIN_FSDP_THRESHOLD
    opt = sgd_momentum(schedules.constant(1e-3), momentum=0.9,
                       momentum_dtype=jnp.bfloat16 if mu_bf16 else None)
    loss_fn = ST.make_lm_loss(cfg, sh)
    step_fn = ST.make_train_step(loss_fn, opt, binarize_mode, DEFAULT_POLICY,
                                 microbatches=cfg.train_microbatches,
                                 compute_dtype=cfg.activation_dtype)

    state_shape = jax.eval_shape(
        lambda: ST.init_train_state(T.init_lm(cfg, jax.random.key(0)), opt))
    st_pspecs = SP.state_pspecs(state_shape["params"], mesh, fsdp)
    st_pspecs = SP.sanitize_pspecs(state_shape, st_pspecs, mesh)
    batch_shape = SP.input_specs(cfg, shape)
    b_pspecs = SP.sanitize_pspecs(batch_shape, SP.batch_pspecs(cfg, shape, mesh), mesh)

    jitted = jax.jit(
        step_fn,
        in_shardings=(_ns(mesh, st_pspecs), _ns(mesh, b_pspecs)),
        out_shardings=(_ns(mesh, st_pspecs), None),
        donate_argnums=0,
    )
    with mesh_context(mesh):
        lowered = jitted.lower(state_shape, batch_shape)
    return lowered, _train_model_flops(cfg, shape), {
        "fsdp": fsdp, "microbatches": cfg.train_microbatches}


def lower_serve(cfg, shape, mesh, packed: bool):
    sh = ShardCtx(mesh)
    params_shape = jax.eval_shape(
        lambda: jax.tree.map(
            lambda x: x.astype(cfg.activation_dtype)
            if x.dtype == jnp.float32 else x,
            T.init_lm(cfg, jax.random.key(0))))
    extra = {"packed": packed}
    if packed:
        from repro.kernels import ops as kops
        from repro.serve.engine import pack_params
        kops.set_use_pallas(False)  # lower the jnp reference body off-TPU
        params_shape = jax.eval_shape(
            lambda: pack_params(T.init_lm(cfg, jax.random.key(0)),
                                DEFAULT_POLICY, "det"))
        fsdp = False  # packed weights are ~16x smaller: TP-only fits
    else:
        fsdp = cfg.param_count() > SERVE_FSDP_THRESHOLD
    extra["fsdp"] = fsdp
    from repro.distributed.sharding import batch_axes
    p_pspecs = SP.sanitize_pspecs(
        params_shape,
        params_pspecs(params_shape, fsdp=fsdp, dp_axes=batch_axes(mesh)), mesh)
    b_shape = SP.input_specs(cfg, shape)
    b_pspecs = SP.sanitize_pspecs(b_shape, SP.batch_pspecs(cfg, shape, mesh),
                                  mesh)

    if shape.kind == "prefill":
        def step_fn(params, tokens):
            logits, cache = T.prefill(cfg, params, tokens, sh,
                                      max_len=shape.seq_len)
            return logits, cache

        cache_ps = SP.cache_pspecs(cfg, cb.ShapeSpec(
            shape.name, shape.seq_len, shape.global_batch, "decode"), mesh)
        out_shape = jax.eval_shape(step_fn, params_shape, b_shape["tokens"])
        cache_ps = SP.sanitize_pspecs(out_shape[1], cache_ps, mesh)
        jitted = jax.jit(
            step_fn,
            in_shardings=(_ns(mesh, p_pspecs), _ns(mesh, b_pspecs["tokens"])),
            out_shardings=(None, _ns(mesh, cache_ps)),
        )
        with mesh_context(mesh):
            lowered = jitted.lower(params_shape, b_shape["tokens"])
        return lowered, _serve_model_flops(cfg, shape, "prefill"), extra

    def step_fn(params, cache, tokens):
        return T.decode_step(cfg, params, cache, tokens, sh)

    jitted = jax.jit(
        step_fn,
        in_shardings=(_ns(mesh, p_pspecs), _ns(mesh, b_pspecs["cache"]),
                      _ns(mesh, b_pspecs["tokens"])),
        out_shardings=(None, _ns(mesh, b_pspecs["cache"])),
        donate_argnums=1,
    )
    with mesh_context(mesh):
        lowered = jitted.lower(params_shape, b_shape["cache"],
                               b_shape["tokens"])
    return lowered, _serve_model_flops(cfg, shape, "decode"), extra


def run_cell(arch: str, shape_name: str, mesh_name: str, binarize_mode: str,
             packed: bool = False, smoke: bool = False) -> dict:
    cfg = cb.get_config(arch, smoke=smoke)
    shape = cb.LM_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    n_chips = mesh.devices.size

    t0 = time.time()
    if shape.kind == "train":
        lowered, model_flops, extra = lower_train(cfg, shape, mesh, binarize_mode)
    else:
        lowered, model_flops, extra = lower_serve(cfg, shape, mesh, packed)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    ma = compiled.memory_analysis()
    mem = {
        "argument_gb": ma.argument_size_in_bytes / 1e9,
        "output_gb": ma.output_size_in_bytes / 1e9,
        "temp_gb": ma.temp_size_in_bytes / 1e9,
        "alias_gb": ma.alias_size_in_bytes / 1e9,
        "code_mb": ma.generated_code_size_in_bytes / 1e6,
    }
    mem["peak_gb"] = (mem["argument_gb"] + mem["output_gb"] + mem["temp_gb"]
                      - mem["alias_gb"])
    ca = compiled.cost_analysis() or {}
    cost = H.analyze(compiled.as_text())
    terms = R.from_hlo_cost(cost, n_chips, model_flops=model_flops,
                            hbm_bytes_per_device=mem["peak_gb"] * 1e9)
    return {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "binarize": binarize_mode, **extra,
        "chips": n_chips,
        "lower_s": t1 - t0, "compile_s": t2 - t1,
        "memory": mem,
        "xla_cost_analysis": {"flops": ca.get("flops"),
                              "bytes": ca.get("bytes accessed")},
        "hlo": cost.as_dict(),
        "roofline": terms.as_dict(),
    }


def cell_filename(arch, shape, mesh, binarize, packed):
    suffix = "__packed" if packed else ""
    return f"{arch}__{shape}__{mesh}__{binarize}{suffix}.json"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--binarize", default="det", choices=["none", "det", "stoch"])
    ap.add_argument("--packed", action="store_true",
                    help="serve with bitpacked binary weights")
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="use reduced configs (debug only)")
    args = ap.parse_args()

    lm_archs = [a for a in cb.ARCH_IDS if a not in ("mnist_fc", "vgg16_cifar10")]
    archs = lm_archs if args.arch == "all" else [cb.canonical_arch(args.arch)]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    os.makedirs(args.out, exist_ok=True)

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        cfg = cb.get_config(arch, smoke=args.smoke)
        shape_names = (list(cb.shapes_for(cfg)) if args.shape == "all"
                       else [args.shape])
        for shape_name in shape_names:
            if shape_name not in cb.shapes_for(cfg):
                print(f"SKIP {arch} x {shape_name}: unsupported "
                      f"(full attention at 500k) — see DESIGN.md")
                continue
            if args.packed and cb.LM_SHAPES[shape_name].kind == "train":
                continue
            for mesh_name in meshes:
                fname = os.path.join(args.out, cell_filename(
                    arch, shape_name, mesh_name, args.binarize, args.packed))
                if os.path.exists(fname) and not args.force:
                    n_skip += 1
                    continue
                try:
                    rec = run_cell(arch, shape_name, mesh_name,
                                   args.binarize, args.packed, args.smoke)
                    with open(fname, "w") as f:
                        json.dump(rec, f, indent=1)
                    r = rec["roofline"]
                    print(f"OK   {arch} x {shape_name} x {mesh_name}: "
                          f"compile={rec['compile_s']:.1f}s "
                          f"peak={rec['memory']['peak_gb']:.2f}GB/dev "
                          f"dominant={r['dominant']} "
                          f"bound={r['bound_time_s']*1e3:.2f}ms "
                          f"mfu_bound={r['mfu_bound'] and round(r['mfu_bound'], 3)}")
                    n_ok += 1
                except Exception:
                    n_fail += 1
                    print(f"FAIL {arch} x {shape_name} x {mesh_name}")
                    traceback.print_exc()
    print(f"\ndry-run: {n_ok} ok, {n_skip} cached, {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
