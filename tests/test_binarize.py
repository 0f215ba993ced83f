"""Unit + property tests for the paper's core technique (Eq. 1-3, Alg. 1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis")
hnp = pytest.importorskip("hypothesis.extra.numpy")
st = pytest.importorskip("hypothesis.strategies")

from repro.core import binarize as B
from repro.core.policy import DEFAULT_POLICY, NONE_POLICY, BinarizePolicy

floats = hnp.arrays(np.float32, hnp.array_shapes(min_dims=1, max_dims=3,
                                                 max_side=16),
                    elements=st.floats(-4, 4, width=32))
# every new example shape compiles anew, which under several test workers
# can exceed hypothesis's default 200 ms per example
no_deadline = hypothesis.settings(deadline=None)


class TestHardSigmoid:
    def test_eq3_values(self):
        # sigma(x) = clip((x+1)/2, 0, 1)
        xs = jnp.array([-3.0, -1.0, -0.5, 0.0, 0.5, 1.0, 3.0])
        expect = jnp.array([0.0, 0.0, 0.25, 0.5, 0.75, 1.0, 1.0])
        np.testing.assert_allclose(B.hard_sigmoid(xs), expect)

    @no_deadline
    @hypothesis.given(floats)
    def test_range(self, w):
        s = np.asarray(B.hard_sigmoid(jnp.asarray(w)))
        assert (s >= 0).all() and (s <= 1).all()


class TestDeterministic:
    def test_eq1_sign_convention(self):
        # w <= 0 -> -1 (including exactly 0), else +1
        w = jnp.array([-2.0, -0.0, 0.0, 1e-9, 2.0])
        np.testing.assert_array_equal(
            B.deterministic_binarize(w), jnp.array([-1, -1, -1, 1, 1.0]))

    @no_deadline
    @hypothesis.given(floats)
    def test_values_are_pm1(self, w):
        wb = np.asarray(B.deterministic_binarize(jnp.asarray(w)))
        assert set(np.unique(wb)).issubset({-1.0, 1.0})

    @no_deadline
    @hypothesis.given(floats)
    def test_idempotent(self, w):
        wb = B.deterministic_binarize(jnp.asarray(w))
        np.testing.assert_array_equal(B.deterministic_binarize(wb), wb)


class TestStochastic:
    def test_eq2_probability(self):
        # empirical P(+1) ~= hard_sigmoid(w)
        for wval in (-0.8, -0.2, 0.0, 0.4, 0.9):
            w = jnp.full((200_000,), wval)
            wb = B.stochastic_binarize(w, jax.random.key(0))
            p_hat = float((wb > 0).mean())
            assert abs(p_hat - float(B.hard_sigmoid(wval))) < 0.01, wval

    def test_saturation_is_deterministic(self):
        w = jnp.array([-1.0, -5.0, 1.0, 5.0])
        wb = B.stochastic_binarize(w, jax.random.key(1))
        np.testing.assert_array_equal(wb, jnp.array([-1.0, -1.0, 1.0, 1.0]))

    def test_reproducible_given_key(self):
        w = jax.random.normal(jax.random.key(2), (128,))
        a = B.stochastic_binarize(w, jax.random.key(3))
        b = B.stochastic_binarize(w, jax.random.key(3))
        np.testing.assert_array_equal(a, b)


class TestSTE:
    def test_gradient_passes_through(self):
        w = jax.random.normal(jax.random.key(0), (32, 16))
        coef = jax.random.normal(jax.random.key(1), (32, 16))

        def loss(w):
            return jnp.sum(B.binarize(w, "det") * coef)

        np.testing.assert_allclose(jax.grad(loss)(w), coef, rtol=1e-6)

    def test_stochastic_ste(self):
        w = jax.random.normal(jax.random.key(0), (64,))

        def loss(w):
            return jnp.sum(B.binarize(w, "stoch", jax.random.key(5)) ** 2
                           + 3.0 * B.binarize(w, "stoch", jax.random.key(5)))

        g = jax.grad(loss)(w)
        wb = B.binarize(w, "stoch", jax.random.key(5))
        np.testing.assert_allclose(g, 2 * wb + 3.0, rtol=1e-5)

    def test_forward_value_is_binary(self):
        w = jax.random.normal(jax.random.key(0), (8, 8))
        wb = np.asarray(B.binarize(w, "det"))
        assert set(np.unique(wb)).issubset({-1.0, 1.0})


class TestClip:
    @no_deadline
    @hypothesis.given(floats)
    def test_bounds(self, w):
        c = np.asarray(B.clip_weights(jnp.asarray(w)))
        assert (c >= -1).all() and (c <= 1).all()

    def test_identity_inside(self):
        w = jnp.array([-0.99, 0.0, 0.5])
        np.testing.assert_array_equal(B.clip_weights(w), w)


class TestTreeAPI:
    def _params(self):
        return {
            "layers": {"attn": {"w_qkv": jnp.ones((4, 8)) * 0.3,
                                "b_qkv": jnp.ones((8,)) * 0.3},
                       "ln1": {"scale": jnp.ones((4,)) * 0.3}},
            "embed": {"embedding": jnp.ones((16, 4)) * 0.3},
        }

    def test_policy_selection(self):
        p = self._params()
        sel = DEFAULT_POLICY.selected_paths(p)
        assert sel == ["layers/attn/w_qkv"]

    def test_binarize_tree_respects_policy(self):
        p = self._params()
        out = B.binarize_tree(p, "det", DEFAULT_POLICY)
        np.testing.assert_array_equal(out["layers"]["attn"]["w_qkv"],
                                      jnp.ones((4, 8)))
        np.testing.assert_array_equal(out["layers"]["ln1"]["scale"],
                                      p["layers"]["ln1"]["scale"])
        np.testing.assert_array_equal(out["embed"]["embedding"],
                                      p["embed"]["embedding"])

    def test_none_mode_is_identity(self):
        p = self._params()
        out = B.binarize_tree(p, "none", DEFAULT_POLICY)
        assert out is p

    def test_clip_tree(self):
        p = {"layers": {"attn": {"w_qkv": jnp.array([[-3.0, 0.5, 3.0]])}},
             "embed": {"embedding": jnp.array([[5.0]])}}
        out = B.clip_tree(p, DEFAULT_POLICY)
        np.testing.assert_array_equal(out["layers"]["attn"]["w_qkv"],
                                      jnp.array([[-1.0, 0.5, 1.0]]))
        # embeddings are not clipped (not selected)
        np.testing.assert_array_equal(out["embed"]["embedding"],
                                      jnp.array([[5.0]]))

    def test_stochastic_tree_needs_key(self):
        with pytest.raises(ValueError):
            B.binarize_tree(self._params(), "stoch", DEFAULT_POLICY)


class TestPolicy:
    def test_none_policy(self):
        assert not NONE_POLICY.selects("layers/attn/w_qkv")

    def test_custom_policy(self):
        pol = BinarizePolicy(include=(r".*kernel$",),
                             exclude=(r"first/kernel",))
        assert pol.selects("second/kernel")
        assert not pol.selects("first/kernel")
        assert not pol.selects("second/bias")
