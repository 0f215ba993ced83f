"""Pallas TPU kernel: matmul against bitpacked binary weights.

The paper's FPGA kernels replace multiply-accumulate with sign-controlled
accumulation because binarized weights are {-1,+1}. The TPU adaptation keeps
the MXU (a matmul is free once operands are in VMEM) and instead attacks the
*memory hierarchy*: weights live in HBM bitpacked (32 weights / int32 word,
16x fewer bytes than bf16), are unpacked to ±1 *inside VMEM per block*, and
fed to the MXU as bf16. The weight-fetch term of the roofline drops ~16x,
which is the dominant term for decode/serving shapes.

Layout: activations  x        (M, K)        bf16/f32
        weights      w_packed (K // 32, N)  int32   (see core.packing)
        scale        optional (N,) f32      (per-output-channel, folds BN/BWN alpha)
        out                   (M, N)        f32 or x.dtype

Block shapes are MXU-aligned: bm, bn multiples of 128 (the systolic array
edge), bk a multiple of 256 so the packed block (bk//32, bn) keeps the int32
sublane dimension >= 8. The f32 accumulator lives in a VMEM scratch buffer
across the K grid dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import PACK


def _unpack_block(words: jax.Array, bk: int, dtype) -> jax.Array:
    """(bk//32, bn) int32 -> (bk, bn) ±1 in ``dtype`` (VMEM-local).

    Bits are extracted in int32 (the TPU has no uint32 -> float cast); the
    sign fill of the arithmetic shift is masked off by ``& 1``."""
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, PACK, 1), 1)
    bits = (words[:, None, :] >> shifts) & 1
    pm1 = (2 * bits - 1).astype(jnp.float32)
    return pm1.reshape(bk, words.shape[-1]).astype(dtype)


def _bmm_kernel(x_ref, wp_ref, o_ref, acc_ref, *, nk: int, bk: int, compute_dtype):
    """Grid (i, j, k): accumulate x[i,k] @ unpack(wp[k,j]) into acc; flush at k end."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_block = _unpack_block(wp_ref[...], bk, compute_dtype)
    acc_ref[...] += jnp.dot(
        x_ref[...].astype(compute_dtype), w_block,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _bmm_scaled_kernel(x_ref, wp_ref, s_ref, o_ref, acc_ref, *, nk: int, bk: int,
                       compute_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_block = _unpack_block(wp_ref[...], bk, compute_dtype)
    acc_ref[...] += jnp.dot(
        x_ref[...].astype(compute_dtype), w_block,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def binary_matmul_pallas(
    x: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array | None = None,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    compute_dtype=jnp.bfloat16,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Blocked Pallas binary matmul. Shapes must divide the block sizes
    (the jit wrapper in ``ops.py`` pads arbitrary shapes first)."""
    m, kdim = x.shape
    k32, n = w_packed.shape
    if k32 * PACK != kdim:
        raise ValueError(f"packed K mismatch: x K={kdim}, packed K={k32 * PACK}")
    if m % block_m or n % block_n or kdim % block_k:
        raise ValueError(
            f"shape ({m},{kdim})x({kdim},{n}) not divisible by blocks "
            f"({block_m},{block_k},{block_n}); use ops.binary_matmul")
    if block_k % PACK:
        raise ValueError("block_k must be a multiple of 32")

    nk = kdim // block_k
    grid = (m // block_m, n // block_n, nk)
    x_spec = pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k))
    w_spec = pl.BlockSpec((block_k // PACK, block_n), lambda i, j, k: (k, j))
    o_spec = pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j))
    scratch = [pltpu.VMEM((block_m, block_n), jnp.float32)]

    if scale is None:
        kern = functools.partial(
            _bmm_kernel, nk=nk, bk=block_k, compute_dtype=compute_dtype)
        in_specs = [x_spec, w_spec]
        args = (x, w_packed)
    else:
        kern = functools.partial(
            _bmm_scaled_kernel, nk=nk, bk=block_k, compute_dtype=compute_dtype)
        s_spec = pl.BlockSpec((1, block_n), lambda i, j, k: (0, j))
        in_specs = [x_spec, w_spec, s_spec]
        args = (x, w_packed, scale.reshape(1, n))

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
