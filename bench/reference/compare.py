"""The comparisons that decide ``correct``.

LM: every served token of the sampled requests is scored by the reference
run once over its prompt and served tokens (teacher-forced). The reading is
the widest gap by which a served token's reference logit lies below the
reference's best at that position: 0 where the program chose the
reference's argmax, small where it chose a near tie. A control computed in a
lower precision reads the gap of the token it puts first at each position.

Classifier: the share of answers (images) whose predicted class is not the
reference's top class.
"""
from __future__ import annotations

import sys
import time

import numpy as np

BUCKET = 512


def _gaps(ref_logits, chosen, valid):
    import jax.numpy as jnp

    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, chosen[..., None], axis=-1)[..., 0]
    return jnp.max(jnp.where(valid, best - got, -jnp.inf))


def lm_readings(model: dict, key, reqs, prompt_len: int,
                quant=None) -> dict:
    """``{"logit_gap": widest gap of the served tokens, "tokens": n}``, and
    ``"control_gap"`` of the ``quant`` forward when given."""
    import jax.numpy as jnp

    from bench.reference.lm import LmReference

    if not reqs:
        return {"logit_gap": float("inf"), "tokens": 0}
    n_max = max(len(r.generated) for r in reqs)
    s = prompt_len + n_max - 1
    s_pad = -(-s // BUCKET) * BUCKET
    seqs = np.zeros((len(reqs), s_pad), np.int32)
    rows = np.zeros((len(reqs), n_max), np.int32)
    chosen = np.zeros((len(reqs), n_max), np.int32)
    valid = np.zeros((len(reqs), n_max), bool)
    for i, r in enumerate(reqs):
        g = np.asarray(r.generated, np.int32)
        seq = np.concatenate([np.asarray(r.prompt, np.int32), g[:-1]])
        seqs[i, :len(seq)] = seq
        rows[i, :len(g)] = prompt_len - 1 + np.arange(len(g))
        rows[i, len(g):] = prompt_len - 1
        chosen[i, :len(g)] = g
        valid[i, :len(g)] = True
    t0 = time.perf_counter()
    ref = LmReference(model, key)
    logits = ref.logits(seqs, rows).block_until_ready()
    print(f"reference: {len(reqs)} x {s_pad} positions in "
          f"{time.perf_counter() - t0:.3f} s", file=sys.stderr, flush=True)
    out = {"logit_gap": float(_gaps(logits, jnp.asarray(chosen),
                                    jnp.asarray(valid))),
           "tokens": int(valid.sum())}
    if quant is not None:
        ctrl = jnp.argmax(ref.logits(seqs, rows, quant), axis=-1)
        out["control_gap"] = float(_gaps(logits, ctrl.astype(jnp.int32),
                                         jnp.asarray(valid)))
    return out


def mismatch_share(preds: np.ndarray, ref_top: np.ndarray) -> float:
    """Share of answers whose class differs from the reference's top."""
    preds, ref_top = np.asarray(preds), np.asarray(ref_top)
    return float(np.mean(preds != ref_top)) if preds.size else float("inf")
