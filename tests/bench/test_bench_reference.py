"""Both plain references agree with the program at CPU sizes, on weights
each draws from the seed on its own."""
import json
import os

import numpy as np
import pytest

from bench.seeds import model_key

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def config(name):
    with open(os.path.join(REPO, "bench", "configs", name)) as f:
        return json.load(f)


def test_lm_reference_matches_the_packed_program():
    import jax

    from bench.reference.lm import LmReference
    from repro.configs.base import ModelConfig
    from repro.core.policy import DEFAULT_POLICY
    from repro.engine import compile_plan
    from repro.models import transformer as T

    m = dict(config("starcoder2_3b-det.json")["model"], n_layers=2,
             d_model=128, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=512,
             vocab_size=512, dtype="float32")
    key = model_key(2**32 + 17)
    mc = ModelConfig(**m)
    params = T.init_lm(mc, key, dtype=mc.activation_dtype)
    packed = compile_plan(params, DEFAULT_POLICY, "det").pack(params)
    toks = jax.random.randint(jax.random.key(3), (2, 24), 1, 512)
    fwd = jax.jit(lambda p, t: T.forward(mc, p, t)[0])
    want = np.asarray(fwd(packed, toks), np.float32)
    rows = np.tile(np.arange(24), (2, 1))
    got = np.asarray(LmReference(m, key).logits(np.asarray(toks), rows))
    assert got.shape == want.shape
    assert np.abs(got - want).max() < 2e-3 * np.abs(want).max()
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.95
    # a different seed draws different weights
    other = np.asarray(LmReference(m, model_key(5)).logits(
        np.asarray(toks), rows))
    assert np.abs(other - want).max() > 0.1 * np.abs(want).max()


def test_vgg_reference_matches_the_xnor_program():
    import jax

    from bench.reference import vgg as ref
    from repro.engine import compile_plan
    from repro.launch.train import make_paper_policy
    from repro.models import vgg

    m = dict(config("vgg16_cifar10-xnor.json")["model"], width_mult=0.125,
             fc=[64, 64, 10], dense_operands="float32",
             conv=[8, 8, "M", 16, 16, "M", 32, 32, 32, "M", 64, 64, 64, "M",
                   64, 64, 64, "M"])
    key = model_key(11)
    tree = jax.jit(lambda k: vgg.init(k, width_mult=0.125))(key)
    plan = compile_plan(tree["params"], make_paper_policy(3), "xnor")
    packed = plan.pack(tree["params"])
    x = jax.random.normal(jax.random.key(1), (4, 32, 32, 3))
    fwd = jax.jit(lambda p, s, x: vgg.apply(p, s, x, training=False,
                                            binary_act=True)[0])
    want = np.asarray(fwd(packed, tree["state"], x))
    got = np.asarray(ref.logits(m, key, x))
    assert np.array_equal(got.argmax(-1), want.argmax(-1))
    assert got == pytest.approx(want, rel=1e-4, abs=1e-4)
