"""Pallas TPU kernel: matmul against bitpacked binary weights.

The paper's FPGA kernels replace multiply-accumulate with sign-controlled
accumulation because binarized weights are {-1,+1}. The TPU adaptation keeps
the MXU and attacks the memory hierarchy: weights live in HBM bitpacked (32
weights / int32 word, 16x fewer bytes than bf16) and are expanded to ±1
inside VMEM, block by block, then fed to the MXU.

Reading the packed weights is cheap (360 MB a step for starcoder2-3b, 0.44
ms at 819 GB/s); expanding them is not. The v5e vector unit has no bf16
arithmetic, so a per-weight shift, mask, ``2b - 1``, int -> f32 -> bf16
chain costs several VPU ops per weight; repeating it for every M-block,
in grid steps of 65K weights each, made this weight stage, not HBM or the
MXU, the kernel's cost. So:

- Each packed word is expanded with integer ops straight to the bit
  patterns of ±1 (``expand_pm1``): for bf16, one shift, mask and xor per
  int32 lane give two weights (bits ``s`` and ``s + 16``), and a bitcast
  splits the lane into two bf16 rows. No int -> float convert per weight.
- The expansion yields K in a fixed order within each 256-row piece
  (``k_order``); the activations' K is permuted to match before the call.
- A call with M <= 512 rows runs one M-block, so each weight is expanded
  once per call; block_n and block_k come from the shape (``choose_blocks``)
  so that a grid step expands ~0.5-1.5 M weights.

Layout: activations  x        (M, K)        bf16/f32
        weights      w_packed (K // 32, N)  int32   (see core.packing)
        scale        optional (N,) f32      (per-output-channel, folds BN/BWN alpha)
        out                   (M, N)        f32 or x.dtype

block_k is a multiple of 256 (one (8, bn) int32 tile of packed words), and
block_n a multiple of 128 or the whole N. The f32 accumulator lives in a
VMEM scratch buffer across the K grid dimension, beside the expanded block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import PACK

PIECE = 8 * PACK      # K rows expanded from one (8, bn) tile of packed words
MAX_BLOCK_M = 512     # above this many rows, M is split into blocks of 256
MAX_BLOCK_N = 512
MAX_BLOCK_K = 3072
VMEM_BUDGET = 12 << 20  # below the v5e's 16 MiB default scoped VMEM

# Per compute dtype: bits of one value, its sign bit in each value-sized
# part of an int32 lane, and the bit pattern of -1 in every part.
_PM1_BITS = {
    jnp.dtype(jnp.bfloat16): (16, 0x80008000, 0xBF80BF80),
    jnp.dtype(jnp.float32): (32, 0x80000000, 0xBF800000),
}


def _as_i32(u: int) -> np.int32:
    return np.uint32(u).view(np.int32)


def k_order(dtype) -> np.ndarray:
    """``order[j]`` is the K offset, within a 256-row piece, of row ``j`` of
    :func:`expand_pm1`'s block: row ``j = (s * 8 + r) * p + h`` holds bit
    ``s + bits * h`` of word ``r``, for ``p = 32 // bits`` values per lane."""
    bits = _PM1_BITS[jnp.dtype(dtype)][0]
    s, r, h = np.meshgrid(np.arange(bits), np.arange(8),
                          np.arange(PACK // bits), indexing="ij")
    return (PACK * r + s + bits * h).reshape(-1)


def permute_k(x: jax.Array, dtype) -> jax.Array:
    """x (M, K), K a multiple of 256 -> x with K in :func:`k_order` within
    each 256-row piece: ``out[:, 256g + j] = x[:, 256g + k_order[j]]``."""
    bits = _PM1_BITS[jnp.dtype(dtype)][0]
    m, k = x.shape
    x = x.reshape(m, k // PIECE, 8, PACK // bits, bits)
    return x.transpose(0, 1, 4, 2, 3).reshape(m, k)


def expand_pm1(words: jax.Array, dtype) -> jax.Array:
    """(8g, bn) int32 packed words -> (256g, bn) ±1 in ``dtype`` (bf16 or
    f32), K in :func:`k_order` within each 256-row piece.

    Plane ``s`` moves bit ``s`` of every value-sized part of the lane to
    that part's sign bit (``<< bits - 1 - s``), keeps the sign bits and
    xors in the pattern of -1: a set bit gives +1, a clear bit -1. The
    planes stack on a major axis (free) and ``pltpu.bitcast`` splits each
    int32 row into ``32 // bits`` rows: low half first."""
    bits, sign, minus_one = _PM1_BITS[jnp.dtype(dtype)]
    r, bn = words.shape
    w = words.reshape(r // 8, 1, 8, bn)
    planes = [((w << (bits - 1 - s)) & _as_i32(sign)) ^ _as_i32(minus_one)
              for s in range(bits)]
    t = jnp.concatenate(planes, axis=1).reshape(r * bits, bn)
    return pltpu.bitcast(t, dtype)


def _bmm_kernel(x_ref, wp_ref, *rest, nk: int, scaled: bool):
    """Grid (i, j, k): expand wp[k, j] into VMEM, accumulate
    x[i, k] @ it into acc; flush (times the scale) at the last k."""
    if scaled:
        s_ref, o_ref, w_ref, acc_ref = rest
    else:
        (o_ref, w_ref, acc_ref), s_ref = rest, None
    k = pl.program_id(2)

    def expand(g, carry):
        rows = pl.ds(pl.multiple_of(g * 8, 8), 8)
        w_ref[pl.ds(pl.multiple_of(g * PIECE, PIECE), PIECE), :] = (
            expand_pm1(wp_ref[rows, :], w_ref.dtype))
        return carry

    jax.lax.fori_loop(0, w_ref.shape[0] // PIECE, expand, 0)
    part = jnp.dot(x_ref[...].astype(w_ref.dtype), w_ref[...],
                   preferred_element_type=jnp.float32)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = part

    @pl.when(k > 0)
    def _accumulate():
        acc_ref[...] += part

    @pl.when(k == nk - 1)
    def _flush():
        out = acc_ref[...]
        if s_ref is not None:
            out = out * s_ref[...].astype(jnp.float32)
        o_ref[...] = out.astype(o_ref.dtype)


def _vmem_bytes(bm, bk, bn, x_bytes, w_bytes, out_bytes) -> int:
    """Double-buffered x, packed and out blocks; the expanded block and
    the f32 accumulator."""
    return (2 * bm * bk * x_bytes + 2 * (bk // PACK) * bn * 4
            + bk * bn * w_bytes + bm * bn * 4 + 2 * bm * bn * out_bytes)


def choose_blocks(m: int, k: int, n: int, *, x_bytes: int, w_bytes: int,
                  out_bytes: int = 4) -> tuple[int, int, int]:
    """(block_m, block_n, block_k) for an (m, k) x (k, n) call whose m, k
    and n are already whole multiples of 16 (or 256 above 512 rows), 256
    and 128 (or n itself).

    One M-block up to 512 rows, so each weight is expanded once per call;
    the widest block_n up to 512 that divides n; then the widest block_k up
    to 3072 that divides k and keeps the working set in VMEM_BUDGET."""
    bm = m if m <= MAX_BLOCK_M else 256
    bn = next((c for c in range(MAX_BLOCK_N, 0, -128) if n % c == 0), n)
    bk = next((c for c in range(min(MAX_BLOCK_K, k), 0, -PIECE)
               if k % c == 0 and _vmem_bytes(bm, c, bn, x_bytes, w_bytes,
                                             out_bytes) <= VMEM_BUDGET),
              PIECE)
    return bm, bn, bk


def binary_matmul_pallas(
    x: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array | None = None,
    *,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    compute_dtype=jnp.bfloat16,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Blocked Pallas binary matmul. Shapes must divide the block sizes
    (the jit wrapper in ``ops.py`` pads arbitrary shapes first); a block
    left as None comes from :func:`choose_blocks`."""
    m, kdim = x.shape
    k32, n = w_packed.shape
    if k32 * PACK != kdim:
        raise ValueError(f"packed K mismatch: x K={kdim}, packed K={k32 * PACK}")
    compute_dtype = jnp.dtype(compute_dtype)
    if compute_dtype not in _PM1_BITS:
        raise ValueError(f"compute dtype {compute_dtype} is not bf16 or f32")
    bm, bn, bk = choose_blocks(
        m, kdim, n, x_bytes=x.dtype.itemsize, w_bytes=compute_dtype.itemsize,
        out_bytes=jnp.dtype(out_dtype).itemsize)
    block_m, block_n, block_k = block_m or bm, block_n or bn, block_k or bk
    if block_k % PIECE:
        raise ValueError(f"block_k must be a multiple of {PIECE}")
    if m % block_m or n % block_n or kdim % block_k:
        raise ValueError(
            f"shape ({m},{kdim})x({kdim},{n}) not divisible by blocks "
            f"({block_m},{block_k},{block_n}); use ops.binary_matmul")

    nk = kdim // block_k
    grid = (m // block_m, n // block_n, nk)
    in_specs = [pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
                pl.BlockSpec((block_k // PACK, block_n), lambda i, j, k: (k, j))]
    args = [permute_k(x, compute_dtype), w_packed]
    if scale is not None:
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)))
        args.append(scale.reshape(1, n))

    return pl.pallas_call(
        functools.partial(_bmm_kernel, nk=nk, scaled=scale is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_k, block_n), compute_dtype),
                        pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(*args)
