"""End-to-end training driver (the paper's "host controller").

Runs real training of any ``--arch`` at any scale that fits the local
devices: the paper models (mnist_fc, vgg16_cifar10) with the paper's recipe
(SGD momentum 0.9, eta0 1e-3, Eq.-4 decay, batch-norm, batch 4), or the LM
architectures (smoke or full configs) with next-token loss on the synthetic
token stream. Fault tolerance is on by default: async checkpoints +
auto-resume; pass --fail-at to watch a simulated crash recover.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch mnist_fc \
      --binarize stoch --steps 500
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m --smoke \
      --binarize det --steps 100 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import json

import jax

from repro.configs import base as cb
from repro.core.policy import DEFAULT_POLICY, NONE_POLICY, BinarizePolicy
from repro.data import synthetic as syn
from repro.ft.failures import FailureInjector
from repro.launch.compile_cache import enable_compile_cache
from repro.models import mnist_fc, transformer as T, vgg
from repro.optim import schedules
from repro.optim.sgd import adamw, sgd_momentum
from repro.train import steps as ST
from repro.train.trainer import Trainer, TrainerConfig

def make_paper_policy(n_fc_layers: int) -> BinarizePolicy:
    """BNN convention (BinaryConnect lineage the paper follows): binarize
    hidden projections; the input layer (first conv / first FC) and the
    classifier head stay full precision. Binarizing the classifier feeds raw
    sign noise into the logits and stalls stochastic training."""
    last = n_fc_layers - 1
    return BinarizePolicy(
        include=(r".*(kernel)$",),
        exclude=(r"(layers|fc)/0/kernel", rf"(layers|fc)/{last}/kernel",
                 r".*bn.*", r"conv/0/kernel"),
    )


def build_paper_model(arch: str, args):
    if arch == "mnist_fc":
        from repro.configs import mnist_fc as C
        hidden = C.SMOKE_HIDDEN if args.smoke else C.HIDDEN
        tree = mnist_fc.init(jax.random.key(args.seed), hidden=hidden)
        apply_fn = mnist_fc.apply
        spec = syn.SyntheticSpec("mnist", n_train=60_000,
                                 batch_size=args.batch or C.BATCH_SIZE,
                                 seed=args.seed)
        recipe = C
    else:
        from repro.configs import vgg16_cifar10 as C
        wm = C.SMOKE_WIDTH_MULT if args.smoke else C.WIDTH_MULT
        tree = vgg.init(jax.random.key(args.seed), width_mult=wm)
        apply_fn = vgg.apply
        spec = syn.SyntheticSpec("cifar", n_train=50_000,
                                 batch_size=args.batch or C.BATCH_SIZE,
                                 seed=args.seed)
        recipe = C

    n_fc = (len(tree["params"]["layers"]) if arch == "mnist_fc"
            else len(tree["params"]["fc"]))
    policy = make_paper_policy(n_fc)
    sched = schedules.paper_eq4(recipe.LEARNING_RATE, spec.steps_per_epoch)
    opt = sgd_momentum(sched, momentum=recipe.MOMENTUM)
    loss_fn = ST.make_classifier_loss(apply_fn)
    step_fn = ST.make_train_step(
        loss_fn, opt, args.binarize,
        policy if args.binarize != "none" else NONE_POLICY,
        has_model_state=True, use_compression=args.compress)
    state = ST.init_train_state(tree["params"], opt, seed=args.seed,
                                model_state=tree["state"],
                                use_compression=args.compress)

    def batch_fn(step):
        x, y = syn.train_batch(spec, step)
        if arch == "mnist_fc":
            x = x.reshape(x.shape[0], -1)
        return {"x": x, "y": y}

    return state, step_fn, batch_fn


def build_lm(arch: str, args):
    cfg = cb.get_config(arch, smoke=args.smoke)
    params = T.init_lm(cfg, jax.random.key(args.seed))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"{cfg.name}: {n_params/1e6:.1f}M params "
          f"(smoke={args.smoke}, binarize={args.binarize})")
    opt = (adamw(schedules.cosine(args.lr, 20, args.steps))
           if args.optimizer == "adamw"
           else sgd_momentum(schedules.constant(args.lr)))
    loss_fn = ST.make_lm_loss(cfg)
    step_fn = ST.make_train_step(
        loss_fn, opt, args.binarize,
        DEFAULT_POLICY if args.binarize != "none" else NONE_POLICY,
        microbatches=args.microbatches, use_compression=args.compress)
    state = ST.init_train_state(params, opt, seed=args.seed,
                                use_compression=args.compress)
    spec = syn.SyntheticSpec("lm", n_train=1 << 30, batch_size=args.batch,
                             seq_len=args.seq, vocab_size=cfg.vocab_size,
                             seed=args.seed)

    def batch_fn(step):
        return {"tokens": syn.lm_tokens(spec, step)}

    return state, step_fn, batch_fn


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--binarize", default="det", choices=["none", "det", "stoch"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "adamw"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--compress", action="store_true",
                    help="1-bit gradient compression with error feedback")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject simulated failures at these steps")
    ap.add_argument("--history-out", default="")
    args = ap.parse_args()

    enable_compile_cache()
    arch = cb.canonical_arch(args.arch)
    if arch in ("mnist_fc", "vgg16_cifar10"):
        state, step_fn, batch_fn = build_paper_model(arch, args)
    else:
        state, step_fn, batch_fn = build_lm(arch, args)

    trainer = Trainer(
        TrainerConfig(total_steps=args.steps,
                      checkpoint_dir=f"{args.ckpt_dir}/{arch}_{args.binarize}",
                      checkpoint_every=args.ckpt_every),
        step_fn, batch_fn, state,
        failure_injector=FailureInjector(tuple(args.fail_at)) if args.fail_at
        else None)
    history = trainer.run()
    last = history[-1] if history else {}
    print(f"done: {len(history)} logged steps, "
          f"recoveries={trainer.recoveries}, final={json.dumps(last)}")
    if args.history_out:
        trainer.save_history(args.history_out)


if __name__ == "__main__":
    main()
