"""Drives the paper's VGG-16 classifier through the program's forward.

Build as ``launch.serve.serve_classifier`` builds it: ``vgg.init`` from the
seed, ``compile_plan(params, make_paper_policy(n_fc), plan).pack``, and the
jitted ``vgg.apply(..., training=False, binary_act=plan == "xnor")``
forward. The window runs back-to-back forwards over a pool of image
batches, keeping ``in_flight`` forwards dispatched, and stamps each batch's
predictions when they reach the host.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import time
from typing import Optional

import numpy as np

from bench.harness import load_module
from bench.seeds import model_key

#: module name of the jitted forward in the device trace
FORWARD = "jit_forward"


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    answers: list          # [(pool index, predictions, arrival time)]
    trace: Optional[object] = None


class System:
    def __init__(self, cfg: dict, seed: int, traced: bool):
        import jax
        import jax.numpy as jnp

        from repro.engine import compile_plan
        from repro.launch.train import make_paper_policy
        from repro.models import vgg

        self.cfg, self.seed = cfg, seed
        self.model = m = cfg["model"]
        # the weights are drawn on the device in one jitted call
        tree = jax.jit(lambda k: vgg.init(k, width_mult=m["width_mult"]))(
            model_key(seed))
        params, self.state = tree["params"], tree["state"]
        plan = compile_plan(params, make_paper_policy(len(params["fc"])),
                            cfg["plan"])
        got = {a.path: a.backend for a in plan.layers}
        want = {f"conv/{i}/kernel": b for i, b in enumerate(m["conv_backends"])}
        want.update({f"fc/{i}/kernel": b
                     for i, b in enumerate(m["fc_backends"])})
        for path, b in want.items():
            if got.get(path) != b:
                raise ValueError(f"plan puts {path} on {got.get(path)!r}, "
                                 f"the configuration says {b!r}")
        self.params = plan.pack(params)
        binary_act = cfg["plan"] == "xnor"

        def forward(p, s, x):
            return vgg.apply(p, s, x, training=False,
                             binary_act=binary_act)[0]

        def predictions(logits):
            return jnp.argmax(logits, axis=-1)

        self.fwd = jax.jit(forward)
        self.pred = jax.jit(predictions)
        self.pool = None

    def warm_up(self, traffic: dict) -> None:
        import jax

        gen = load_module(f"bench/traffic/{traffic['generator']}.py")
        pool = gen.image_pool(traffic,
                              jax.random.fold_in(model_key(self.seed), 1))
        self.pool = [pool[i] for i in range(pool.shape[0])]
        jax.block_until_ready(self.pool)
        np.asarray(self.pred(self.fwd(self.params, self.state, self.pool[0])))

    def window(self, traffic: dict, seconds: float,
               traced: bool) -> Window:
        depth = int(traffic.get("in_flight", 2))
        trace_s = min(float(traffic.get("trace_seconds", seconds)), seconds)
        answers, pending = [], collections.deque()
        tw = None
        t_open = time.perf_counter()
        t_close = t_open + seconds
        i = 0
        try:
            while True:
                now = time.perf_counter()
                if now >= t_close:
                    break
                if traced and tw is None and now >= t_close - trace_s:
                    from bench.trace.tracer import TraceWindow

                    tw = TraceWindow()
                j = i % len(self.pool)
                pending.append((j, self.pred(self.fwd(
                    self.params, self.state, self.pool[j]))))
                i += 1
                if len(pending) >= depth:
                    j, p = pending.popleft()
                    preds = np.asarray(p)
                    answers.append((j, preds, time.perf_counter()))
            trace = tw.stop() if tw is not None else None
        except BaseException:
            if tw is not None:
                tw.abandon()
            raise
        # answers in flight at the close are late, not lost: wait for them
        for j, p in pending:
            answers.append((j, np.asarray(p), time.perf_counter()))
        return Window(t_open, t_close, answers, trace)

    @staticmethod
    def end_to_end(win: Window, traffic: dict) -> tuple[dict, int, int]:
        t0, t1 = win.t_open, win.t_close
        done = sum(len(p) for _, p, t in win.answers if t0 <= t <= t1)
        attempted = sum(len(p) for _, p, _ in win.answers)
        return {"images_per_s": done / (t1 - t0)}, attempted, 0

    def release(self) -> None:
        self.params = self.state = self.fwd = None
        gc.collect()

    def check(self, win: Window, traffic: dict,
              quant: Optional[str] = None) -> dict:
        from bench.reference import vgg as ref
        from bench.reference.compare import mismatch_share

        key = model_key(self.seed)
        top = [np.asarray(ref.logits(self.model, key, x).argmax(-1))
               for x in self.pool]
        preds = np.concatenate([p for _, p, _ in win.answers]) \
            if win.answers else np.zeros((0,), np.int32)
        want = np.concatenate([top[j] for j, _, _ in win.answers]) \
            if win.answers else np.zeros((0,), np.int32)
        out = {"top1_mismatch": mismatch_share(preds, want),
               "images": int(preds.size)}
        if quant is not None:
            ctrl = np.concatenate([np.asarray(ref.logits(
                self.model, key, x, quant).argmax(-1)) for x in self.pool])
            out["control_mismatch"] = mismatch_share(ctrl,
                                                     np.concatenate(top))
            # the same answers against a reference whose dense layers keep
            # float32 operands: shows which precision the chip's default is
            f32 = dict(self.model, dense_operands="float32")
            top32 = [np.asarray(ref.logits(f32, key, x).argmax(-1))
                     for x in self.pool]
            out["control_mismatch_f32_reference"] = mismatch_share(
                preds, np.concatenate([top32[j] for j, _, _ in win.answers]))
        return out
