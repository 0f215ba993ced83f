"""Pallas TPU kernels for the fully-binary compute path.

Two kernels, mirroring the paper's FPGA pipeline:

* ``sign_pack_pallas`` — fused sign-binarize (Eq. 1) + bitpack of activations
  along the last axis, ``(M, K) f32/bf16 -> (M, K//32) int32``. Fusing the
  two means the full-width activation never round-trips through HBM between
  binarization and the matmul: only the 1-bit packed words leave the chip.

* ``xnor_matmul_pallas`` — the XNOR-popcount matmul over packed operands:

      dot[m, n] = K - 2 * sum_j popcount(a[m, j] XOR w[j, n])

  with an int32 VMEM accumulator carried across the K grid dimension. This
  is pure VPU integer work (XOR + popcount + add) — the TPU analogue of the
  paper's DSP-free XNOR/popcount datapath; no MXU, no floating point until
  the optional per-channel scale at flush.

Layouts: a_packed (M, K//32) int32   (xnor.packing — packed along last axis)
         w_packed (K//32, N) int32   (core.packing — packed along first axis)
         out      (M, N)     int32, or f32 when a scale is fused.

``k_total`` is the *true* contraction length: 0-bit padding on both operands
XORs to 0, contributes nothing to the popcount, and drops out of the formula
(see xnor.packing). Block constraints: block_m a multiple of 8; a packed
word dim that is the last (lane) dim of a block — a's K//32 and sign_pack's
output — must be whole or a multiple of 128 words on a TPU
(:func:`lane_words` picks such a block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.compat import ceil_to
from repro.core.packing import PACK

LANES = 128


def lane_words(k32: int, words: int) -> int:
    """Packed words per block along a lane (last) dim of ``k32`` words that
    the TPU block rule accepts: the whole dim when it fits in 128 lanes,
    else ``words`` rounded up to a multiple of 128 (the caller pads the dim
    to a multiple of the result with 0-bit words)."""
    return k32 if k32 <= LANES else ceil_to(words, LANES)


def _block_popcount_dot(a_words: jax.Array, w_words: jax.Array) -> jax.Array:
    """(bm, bk32) x (bk32, bn) packed words -> (bm, bn) int32 XOR-popcount sum.

    One word at a time, an (bm, 1) column of ``a`` against a (1, bn) row of
    ``w``, so every intermediate is one (bm, bn) tile. Statically unrolled:
    the TPU lowering slices lanes only at offsets it knows."""
    acc = jnp.zeros((a_words.shape[0], w_words.shape[1]), jnp.int32)
    for j in range(a_words.shape[1]):
        acc += jax.lax.population_count(a_words[:, j:j + 1]
                                        ^ w_words[j:j + 1, :])
    return acc


def _xnor_kernel(a_ref, w_ref, o_ref, acc_ref, *, nk: int, k_total: int):
    """Grid (i, j, k): accumulate popcounts into acc; emit K - 2*acc at k end."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _block_popcount_dot(a_ref[...], w_ref[...])

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = (k_total - 2 * acc_ref[...]).astype(o_ref.dtype)


def _xnor_scaled_kernel(a_ref, w_ref, s_ref, o_ref, acc_ref, *, nk: int,
                        k_total: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _block_popcount_dot(a_ref[...], w_ref[...])

    @pl.when(k == nk - 1)
    def _flush():
        dot = (k_total - 2 * acc_ref[...]).astype(jnp.float32)
        o_ref[...] = (dot * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def xnor_matmul_pallas(
    a_packed: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array | None = None,
    *,
    k_total: int,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Blocked XNOR-popcount matmul. Shapes must divide the block sizes
    (the jit wrapper in ``ops.py`` pads arbitrary shapes first)."""
    m, k32 = a_packed.shape
    k32w, n = w_packed.shape
    if k32 != k32w:
        raise ValueError(f"packed K mismatch: a has {k32} words, w has {k32w}")
    if block_k % PACK:
        raise ValueError("block_k must be a multiple of 32")
    bk32 = block_k // PACK
    if m % block_m or n % block_n or k32 % bk32:
        raise ValueError(
            f"packed shape ({m},{k32})x({k32w},{n}) not divisible by blocks "
            f"({block_m},{bk32},{block_n}); use ops.xnor_matmul")
    if out_dtype is None:
        out_dtype = jnp.int32 if scale is None else jnp.float32

    nk = k32 // bk32
    grid = (m // block_m, n // block_n, nk)
    a_spec = pl.BlockSpec((block_m, bk32), lambda i, j, k: (i, k))
    w_spec = pl.BlockSpec((bk32, block_n), lambda i, j, k: (k, j))
    o_spec = pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j))
    scratch = [pltpu.VMEM((block_m, block_n), jnp.int32)]

    if scale is None:
        kern = functools.partial(_xnor_kernel, nk=nk, k_total=k_total)
        in_specs = [a_spec, w_spec]
        args = (a_packed, w_packed)
    else:
        kern = functools.partial(_xnor_scaled_kernel, nk=nk, k_total=k_total)
        s_spec = pl.BlockSpec((1, block_n), lambda i, j, k: (0, j))
        in_specs = [a_spec, w_spec, s_spec]
        args = (a_packed, w_packed, scale.reshape(1, n))

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(*args)


def _sign_pack_kernel(x_ref, o_ref):
    """(bm, bk) float -> (bm, bk//32) int32: Eq. (1) sign bit, packed lanes.

    Splitting the lane dim into (words, 32) has no TPU lowering, so the
    pack is a matmul on the MXU: ``bits @ P`` with ``P[32j + b, j] = 2**b``.
    The low and high 16 bits go through separate bf16 matmuls, so every
    product and every f32 partial sum is an exact integer below 2**16."""
    # compared in f32: the v5e vector unit has no bf16 compare
    bits = (x_ref[...].astype(jnp.float32) > 0).astype(jnp.bfloat16)
    bk = bits.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (bk, bk // PACK), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bk, bk // PACK), 1)
    bit = row % PACK
    mine = row // PACK == col
    weight = (1 << (bit % 16)).astype(jnp.float32)

    def half(sel):
        p = jnp.where(mine & sel, weight, 0.0).astype(jnp.bfloat16)
        return jnp.dot(bits, p,
                       preferred_element_type=jnp.float32).astype(jnp.int32)

    o_ref[...] = (half(bit >= 16) << 16) | half(bit < 16)


def sign_pack_pallas(
    x: jax.Array,
    *,
    block_m: int = 128,
    block_k: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused sign-binarize + bitpack: (M, K) -> (M, K//32) int32.
    M % block_m == 0, K % block_k == 0, block_k % 32 == 0; ``block_k``
    defaults to the whole K (:func:`sign_pack_rows` pads any shape)."""
    m, kdim = x.shape
    block_k = kdim if block_k is None else block_k
    if m % block_m or kdim % block_k or block_k % PACK:
        raise ValueError(f"bad blocks ({block_m},{block_k}) for shape {(m, kdim)}")
    grid = (m // block_m, kdim // block_k)
    x_spec = pl.BlockSpec((block_m, block_k), lambda i, j: (i, j))
    o_spec = pl.BlockSpec((block_m, block_k // PACK), lambda i, j: (i, j))
    return pl.pallas_call(
        _sign_pack_kernel,
        grid=grid,
        in_specs=[x_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((m, kdim // PACK), jnp.int32),
        interpret=interpret,
    )(x)


def sign_pack_rows(x2: jax.Array, *, block_m: int = 128, block_k: int = 512,
                   interpret: bool = False) -> jax.Array:
    """:func:`sign_pack_pallas` on any (M, K) with K % 32 == 0: pads M and K
    to TPU-legal blocks (0 packs to bit 0, then is sliced off)."""
    m, kdim = x2.shape
    k32 = kdim // PACK
    bk32 = lane_words(k32, block_k // PACK)
    bm = min(block_m, ceil_to(m, 8))
    mp, kp = ceil_to(m, bm), ceil_to(k32, bk32) * PACK
    xp = jnp.pad(x2, ((0, mp - m), (0, kp - kdim)))
    packed = sign_pack_pallas(xp, block_m=bm, block_k=bk32 * PACK,
                              interpret=interpret)
    return packed[:m, :k32]
