"""Pallas TPU kernel: fused (stochastic|deterministic) binarize + bitpack.

FPGA stochastic BNNs use on-fabric LFSRs to draw the Bernoulli samples of
Eq. (2). Here the uniform random words are an *operand* (``bits``, drawn by
the caller with ``jax.random.bits``), so the packed replica depends only on
the key and is the same on every backend. The kernel body thresholds them
against hard_sigmoid(w) in fixed point and packs 32 rows into one int32
word.

Layout: w     (K, N) f32/bf16 master weights
        bits  (K, N) uint32 uniform random words (stochastic only)
        out   (K // 32, N) int32 packed sign bits (+1 -> 1)

The threshold is computed in uint32 fixed point: P(bit=1) = sigma(w) and
``bits < sigma(w) * 2^32`` has exactly that probability for uniform words.
The clip endpoints are handled exactly: p = 1 (w >= +1, a value master-weight
clipping produces) must yield bit 1 for *every* random word, but the f32
comparison alone cannot guarantee it — words >= 2^32 - 128 round up to
2^32.0f and tie with the threshold — so the kernels force the p >= 1 lane
explicitly. p = 0 (w <= -1) is exact as-is (u < 0 never holds).

The TPU vector unit has no unsigned reductions and no uint32 -> float cast,
so the kernel works in int32: the random words are bitcast to int32 before
the call, and packing sums disjoint bits in int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.packing import PACK

_TWO32 = 4294967296.0  # 2 ** 32


def _pack_block(ones: jax.Array, bk: int) -> jax.Array:
    """(bk, bn) int32 {0,1} -> (bk//32, bn) int32 packed words.

    The 32 shifted bits of a word are disjoint, so their int32 sum is their
    OR: no carry ever happens, and bit 31 lands on the sign bit."""
    bn = ones.shape[-1]
    b = ones.reshape(bk // PACK, PACK, bn)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, PACK, 1), 1)
    return jnp.sum(b << shifts, axis=1)


def _word_to_f32(x: jax.Array) -> jax.Array:
    """A uint32 word held bitcast in int32 -> its value as f32, rounded like
    a uint32 -> f32 cast: both 16-bit halves are exact in f32, so their sum
    rounds once, to nearest."""
    hi = ((x >> 16) & 0xFFFF).astype(jnp.float32)
    lo = (x & 0xFFFF).astype(jnp.float32)
    return hi * 65536.0 + lo


def _stoch_kernel(w_ref, bits_ref, o_ref, *, bk: int):
    w = w_ref[...].astype(jnp.float32)
    p = jnp.clip((w + 1.0) * 0.5, 0.0, 1.0)            # Eq. (3)
    thresh = (p * _TWO32).astype(jnp.float32)
    u = _word_to_f32(bits_ref[...])                     # uniform in [0, 2^32)
    # p >= 1 forced: u rounds to 2^32.0f for the top 128 words and would
    # tie with the threshold, turning a sure bit into a 3e-8 miss
    ones = ((u < thresh) | (p >= 1.0)).astype(jnp.int32)  # P(one) = p (Eq. 2)
    o_ref[...] = _pack_block(ones, bk)


def _det_kernel(w_ref, o_ref, *, bk: int):
    # compared in f32: the v5e vector unit has no bf16 compare
    ones = (w_ref[...].astype(jnp.float32) > 0).astype(jnp.int32)  # Eq. (1)
    o_ref[...] = _pack_block(ones, bk)


def binarize_pack_pallas(
    w: jax.Array,
    bits: jax.Array | None = None,
    *,
    stochastic: bool,
    block_k: int = 256,
    block_n: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Fused binarize+pack. ``w`` is (K, N) with K % block_k == 0,
    N % block_n == 0, block_k % 32 == 0 (ops.py pads arbitrary shapes)."""
    kdim, n = w.shape
    if kdim % block_k or n % block_n or block_k % PACK:
        raise ValueError(f"bad blocks for shape {(kdim, n)}")
    grid = (kdim // block_k, n // block_n)
    w_spec = pl.BlockSpec((block_k, block_n), lambda i, j: (i, j))
    o_spec = pl.BlockSpec((block_k // PACK, block_n), lambda i, j: (i, j))
    out_shape = jax.ShapeDtypeStruct((kdim // PACK, n), jnp.int32)

    if not stochastic:
        return pl.pallas_call(
            functools.partial(_det_kernel, bk=block_k),
            grid=grid, in_specs=[w_spec], out_specs=o_spec,
            out_shape=out_shape, interpret=interpret,
        )(w)

    if bits is None:
        raise ValueError("stochastic=True requires bits")
    bits_spec = pl.BlockSpec((block_k, block_n), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_stoch_kernel, bk=block_k),
        grid=grid, in_specs=[w_spec, bits_spec], out_specs=o_spec,
        out_shape=out_shape, interpret=interpret,
    )(w, jax.lax.bitcast_convert_type(bits, jnp.int32))
