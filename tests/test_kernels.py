"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs the jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis; "
    "tests/test_xnor.py covers the kernels without it")
st = pytest.importorskip("hypothesis.strategies")

from repro.core import packing as P
from repro.kernels import binary_matmul as BM, ops, ref
from repro.kernels.binary_matmul import binary_matmul_pallas
from repro.kernels.stoch_binarize import binarize_pack_pallas


class TestPacking:
    @hypothesis.given(st.integers(1, 8), st.integers(1, 33))
    @hypothesis.settings(max_examples=20, deadline=None)
    def test_roundtrip(self, k32, n):
        key = jax.random.key(k32 * 100 + n)
        pm1 = jnp.where(jax.random.bernoulli(key, 0.5, (k32 * 32, n)), 1., -1.)
        np.testing.assert_array_equal(P.unpack_bits(P.pack_bits(pm1)), pm1)

    def test_pad_to_pack(self):
        w = jnp.ones((33, 4))
        wp = P.pad_to_pack(w)
        assert wp.shape == (64, 4)
        np.testing.assert_array_equal(wp[33:], -jnp.ones((31, 4)))

    def test_compression_ratio(self):
        assert P.compression_ratio((1024, 1024), dtype_bytes=2) == 16.0
        assert P.compression_ratio((1024, 1024), dtype_bytes=4) == 32.0


# (M, K, N) sweeps: MXU-aligned, ragged, tiny.
MATMUL_SHAPES = [
    (128, 512, 128), (256, 1024, 384), (200, 512, 100), (8, 512, 128),
    (128, 544, 128),  # K not multiple of block but multiple of 32
]


class TestBinaryMatmulKernel:
    @pytest.mark.parametrize("m,k,n", MATMUL_SHAPES)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, m, k, n, dtype):
        kx, kw = jax.random.split(jax.random.key(m * k + n))
        x = jax.random.normal(kx, (m, k), jnp.float32).astype(dtype)
        wp = ops.binarize_and_pack(jax.random.normal(kw, (k, n)))
        # ops picks compute dtype from the input (f32 in / f32 compute);
        # compare the oracle under the same compute dtype
        cd = jnp.float32 if dtype == jnp.float32 else jnp.bfloat16
        got = ops.binary_matmul(x, wp, block_k=256)
        want = ref.binary_matmul_ref(x, wp, compute_dtype=cd)
        # f32 kernel accumulates per K-block: summation-order noise ~1e-4
        tol = 1e-3 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=tol, atol=tol)

    def test_matches_dense_matmul(self):
        x = jax.random.normal(jax.random.key(0), (256, 512))
        w = jax.random.normal(jax.random.key(1), (512, 256))
        wp = ops.binarize_and_pack(w)
        dense = x @ jnp.where(w > 0, 1., -1.)
        got = ops.binary_matmul(x, wp, block_k=256)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(dense, np.float32),
                                   rtol=1e-4, atol=1e-3)

    def test_scaled(self):
        x = jax.random.normal(jax.random.key(2), (128, 512))
        wp = ops.binarize_and_pack(jax.random.normal(jax.random.key(3), (512, 128)))
        s = jax.random.uniform(jax.random.key(4), (128,), minval=0.5, maxval=2.0)
        got = ops.binary_matmul(x, wp, s, block_k=256)
        want = ref.binary_matmul_ref(x, wp, s, compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-3)

    def test_batched_leading_dims(self):
        x = jax.random.normal(jax.random.key(5), (4, 32, 512))
        wp = ops.binarize_and_pack(jax.random.normal(jax.random.key(6), (512, 64)))
        got = ops.binary_matmul(x, wp)
        assert got.shape == (4, 32, 64)
        want = ref.binary_matmul_ref(
            x.reshape(-1, 512), wp,
            compute_dtype=jnp.float32).reshape(4, 32, 64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-3)

    def test_block_spec_direct(self):
        """Direct pallas_call with exact blocks (no padding path)."""
        x = jax.random.normal(jax.random.key(7), (256, 1024))
        wp = ops.binarize_and_pack(jax.random.normal(jax.random.key(8), (1024, 256)))
        got = binary_matmul_pallas(x, wp, block_m=128, block_n=128,
                                   block_k=256, interpret=True)
        want = ref.binary_matmul_ref(x, wp)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-2, atol=3e-2)


# starcoder2-3b's four projections (K, N): qkv, o, up, down
LM_KN = [(3072, 3584), (3072, 3072), (3072, 12288), (12288, 3072)]
# (compute dtype, scaled): each (K, N) meets all four across its four M
_MODES = [(jnp.bfloat16, True), (jnp.float32, False), (jnp.bfloat16, False),
          (jnp.float32, True)]
KERNEL_CASES = [
    pytest.param(m, k, n, *_MODES[(i + j) % 4], 1,
                 id=f"m{m}-{k}x{n}-{_MODES[(i + j) % 4][0].__name__}"
                    f"{'-scaled' if _MODES[(i + j) % 4][1] else ''}")
    for i, (k, n) in enumerate(LM_KN) for j, m in enumerate((16, 32, 256, 272))
] + [
    pytest.param(200, 544, 100, jnp.bfloat16, True, 1, id="ragged-bf16-scaled"),
    pytest.param(40, 800, 300, jnp.float32, False, 1, id="ragged-f32"),
    pytest.param(520, 512, 384, jnp.bfloat16, False, 1, id="m520-two-mblocks"),
    pytest.param(16, 3072, 3584, jnp.bfloat16, True, 3, id="vmap-replicas-bf16"),
    pytest.param(24, 1024, 640, jnp.float32, True, 2, id="vmap-replicas-f32"),
]


def _integer_x(key, shape, dtype):
    """Small integer activations: every product and partial sum is an
    integer below 2**24, exact in bf16 and f32 in any order, so the kernel
    must equal the reference bit for bit."""
    x = jnp.round(jax.random.normal(key, shape) * 3)
    return jnp.clip(x, -8, 8).astype(dtype)


@pytest.mark.parametrize("m,k,n,dtype,scaled,replicas", KERNEL_CASES)
def test_binary_matmul_kernel_exact(m, k, n, dtype, scaled, replicas):
    """ops.binary_matmul (interpret mode) == binary_matmul_ref exactly at
    the LM's widths, decode and chunk M, bf16 and f32 compute, scaled or
    not, ragged shapes, and a vmapped replica stack."""
    kx, kw, ks = jax.random.split(jax.random.key(m * 7 + k + n), 3)
    lead = (replicas,) if replicas > 1 else ()
    x = _integer_x(kx, lead + (m, k), dtype)
    wp = jax.random.bits(kw, lead + (-(-k // 32), n), jnp.uint32).astype(
        jnp.int32)
    s = (jax.random.uniform(ks, lead + (n,), minval=0.5, maxval=2.0)
         if scaled else None)
    want_fn = lambda x, wp, s: ref.binary_matmul_ref(  # noqa: E731
        x, wp, s, compute_dtype=dtype)
    if replicas > 1:
        got = jax.vmap(lambda x, wp, s: ops.binary_matmul(x, wp, s))(x, wp, s)
        want = jax.vmap(want_fn)(x, wp, s)
    else:
        got, want = ops.binary_matmul(x, wp, s), want_fn(x, wp, s)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_expand_pm1_is_unpack_bits(dtype):
    """The kernel's weight stage, alone: its ±1 block, read in k_order
    within each 256-row piece, is core.packing.unpack_bits bit for bit."""
    words = jax.random.bits(jax.random.key(3), (24, 256), jnp.uint32).astype(
        jnp.int32)
    words = words.at[0, :4].set(jnp.array([0, -1, 1, -2 ** 31], jnp.int32))
    got = jax.jit(BM.expand_pm1, static_argnums=1)(words, dtype)
    assert got.dtype == dtype and got.shape == (768, 256)
    order = BM.k_order(dtype)
    assert sorted(order) == list(range(BM.PIECE))
    want = P.unpack_bits(words, dtype).reshape(3, BM.PIECE, 256)[:, order]
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want).reshape(768, 256))
    x = jnp.arange(2 * 768, dtype=jnp.float32).reshape(2, 768)
    np.testing.assert_array_equal(
        np.asarray(BM.permute_k(x, dtype)).reshape(2, 3, BM.PIECE),
        np.asarray(x).reshape(2, 3, BM.PIECE)[:, :, order])


def _pallas_grids(jaxpr):
    """Grids of every pallas_call in a (closed) jaxpr, nested ones too."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for v in eqn.params.values():
            if isinstance(v, ClosedJaxpr):
                grids += _pallas_grids(v.jaxpr)
            elif isinstance(v, Jaxpr):
                grids += _pallas_grids(v)
    return grids


def _grids(m, k, n, dtype=jnp.bfloat16, **blocks):
    x = jax.ShapeDtypeStruct((m, k), dtype)
    wp = jax.ShapeDtypeStruct((-(-k // 32), n), jnp.int32)
    return _pallas_grids(jax.make_jaxpr(
        lambda x, wp: ops.binary_matmul(x, wp, **blocks))(x, wp).jaxpr)


@pytest.mark.parametrize("m", [1, 8, 16, 17, 32, 100, 256, 272, 500, 512])
@pytest.mark.parametrize("k,n", LM_KN)
def test_one_m_block_up_to_512_rows(m, k, n):
    """Every M <= 512 runs one M-block, so each weight is expanded once per
    call; the grid step expands at least 256K weights (block_k x block_n)."""
    (grid,) = _grids(m, k, n)
    assert grid[0] == 1
    assert (k // grid[2]) * (n // grid[1]) >= 256 * 1024


@pytest.mark.parametrize("m,k,n,kernel", [
    (8, 512, 128, False),          # tiny
    (16, 512, 1023, False),        # one MAC below 128 * 128 * 512 ...
    (128, 512, 127, False),
    (16, 512, 1024, True),         # ... and at it
    (128, 512, 128, True),
    (513, 512, 128, True),         # above 512 rows: M-blocks of 256
    (1024, 3072, 3584, True),
])
def test_fallback_threshold_and_m_blocks(m, k, n, kernel):
    """Below MIN_KERNEL_MACS (= 128 * 128 * 512, the parent's default
    blocks) the jnp reference runs, at and above it the kernel, as before
    blocks came from the shape; past 512 rows M splits into blocks of 256."""
    assert ops.MIN_KERNEL_MACS == 128 * 128 * 512
    grids = _grids(m, k, n)
    assert len(grids) == int(kernel)
    if kernel:
        assert grids[0][0] == (1 if m <= 512 else -(-m // 256))


def test_explicit_blocks_are_honoured():
    (grid,) = _grids(256, 1024, 384, block_k=256)
    assert grid[2] == 1024 // 256
    (grid,) = _grids(256, 1024, 384, block_m=128, block_n=128)
    assert grid[:2] == (2, 3)


class TestBinarizePackKernel:
    @pytest.mark.parametrize("k,n", [(256, 256), (512, 384), (300, 100)])
    def test_det_matches_oracle(self, k, n):
        w = jax.random.normal(jax.random.key(k + n), (k, n))
        got = ops.binarize_and_pack(w, stochastic=False)
        want = ref.det_binarize_pack_ref(P.pad_to_pack(w))[:, :n]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_stoch_matches_oracle_same_bits(self):
        w = jax.random.normal(jax.random.key(0), (512, 256))
        key = jax.random.key(42)
        got = ops.binarize_and_pack(w, key, stochastic=True)
        bits = jax.random.bits(key, (512, 256), jnp.uint32)
        want = ref.stoch_binarize_pack_ref(w, bits)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_stoch_distribution(self):
        w = jnp.full((512, 512), 0.5)  # P(+1) = 0.75
        packed = ops.binarize_and_pack(w, jax.random.key(1), stochastic=True)
        frac = float((P.unpack_bits(packed) > 0).mean())
        assert abs(frac - 0.75) < 0.01

    def test_det_pallas_direct(self):
        w = jax.random.normal(jax.random.key(2), (512, 512))
        got = binarize_pack_pallas(w, stochastic=False, interpret=True)
        want = ref.det_binarize_pack_ref(w)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_roundtrip_through_matmul(self):
        """Pack with the kernel, multiply with the kernel: end-to-end."""
        w = jax.random.normal(jax.random.key(3), (512, 128))
        x = jax.random.normal(jax.random.key(4), (64, 512))
        wp = ops.binarize_and_pack(w)
        got = ops.binary_matmul(x, wp, block_k=256)
        want = x @ jnp.where(w > 0, 1., -1.)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(want, np.float32),
                                   rtol=1e-4, atol=1e-3)
