"""Public jit'd wrappers around the Pallas kernels.

Handle arbitrary shapes (pad to block multiples, slice back), batch leading
dims, pick interpret mode automatically on non-TPU backends, and fall back to
the jnp reference for shapes too small to block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.compat import ceil_to as _ceil_to, on_tpu as _on_tpu
from repro.core.packing import PACK, pack_bits, pad_to_pack
from repro.kernels import ref
from repro.kernels.binary_matmul import (MAX_BLOCK_M, PIECE,
                                         binary_matmul_pallas)
from repro.kernels.stoch_binarize import binarize_pack_pallas


# Below this many multiply-adds (M * N * K) ``binary_matmul`` takes the jnp
# reference: padding to whole kernel blocks would exceed the work.
MIN_KERNEL_MACS = 128 * 128 * 512

# Global default for the use_pallas dispatch (dry-runs lower the jnp
# reference body off-TPU for clean HLO; real-TPU serving keeps the kernel).
_DEFAULT_USE_PALLAS = True


def set_use_pallas(value: bool) -> None:
    global _DEFAULT_USE_PALLAS
    _DEFAULT_USE_PALLAS = value


def binary_matmul(
    x: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array | None = None,
    *,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    out_dtype=jnp.float32,
    use_pallas: bool | None = None,
    compute_dtype=None,
) -> jax.Array:
    """``x @ unpack(w_packed) [* scale]`` for x of shape (..., K).

    Uses the Pallas kernel (interpret mode off-TPU) with padding to block
    multiples; falls back to the jnp reference below ``MIN_KERNEL_MACS``
    (tiny shapes). Blocks left as None come from the padded shape
    (``binary_matmul.choose_blocks``). ``compute_dtype`` defaults to the
    input dtype for f32 activations (numerical parity with the dense path)
    and bf16 otherwise (the MXU-native choice)."""
    if use_pallas is None:
        use_pallas = _DEFAULT_USE_PALLAS
    if compute_dtype is None:
        compute_dtype = jnp.float32 if x.dtype == jnp.float32 else jnp.bfloat16
    return _binary_matmul(x, w_packed, scale, block_m=block_m,
                          block_n=block_n, block_k=block_k,
                          out_dtype=out_dtype, use_pallas=use_pallas,
                          compute_dtype=compute_dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "out_dtype",
                              "use_pallas", "compute_dtype"))
def _binary_matmul(
    x: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array | None = None,
    *,
    block_m: int | None,
    block_n: int | None,
    block_k: int | None,
    out_dtype,
    use_pallas: bool,
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    *lead, kdim = x.shape
    k32, n = w_packed.shape
    if k32 * PACK != kdim:
        raise ValueError(f"K mismatch: x has K={kdim}, packed has {k32 * PACK}")
    x2 = x.reshape(-1, kdim)
    m = x2.shape[0]
    if not use_pallas or m * n * kdim < MIN_KERNEL_MACS:
        out = ref.binary_matmul_ref(x2, w_packed, scale, out_dtype=out_dtype,
                                    compute_dtype=compute_dtype)
        return out.reshape(*lead, n)

    if block_m:
        block_m = min(block_m, _ceil_to(m, 8))
        mp = _ceil_to(m, block_m)
    else:  # one M-block up to MAX_BLOCK_M rows, blocks of 256 above
        mp = _ceil_to(m, 16 if m <= MAX_BLOCK_M else 256)
    np_, kp = _ceil_to(n, block_n or 128), _ceil_to(kdim, block_k or PIECE)
    xp = jnp.pad(x2, ((0, mp - m), (0, kp - kdim)))
    wp = jnp.pad(w_packed, ((0, (kp - kdim) // PACK), (0, np_ - n)))
    sp = None if scale is None else jnp.pad(scale, (0, np_ - n))
    out = binary_matmul_pallas(
        xp, wp, sp,
        block_m=block_m, block_n=block_n, block_k=block_k,
        compute_dtype=compute_dtype,
        out_dtype=out_dtype, interpret=not _on_tpu(),
    )
    # Padded K rows contribute unpack(0-bits) = -1 weights times zero
    # activations = 0, so no correction is needed.
    return out[:m, :n].reshape(*lead, n)


@functools.partial(jax.jit, static_argnames=("stochastic", "block_k", "block_n"))
def binarize_and_pack(
    w: jax.Array,
    key: jax.Array | None = None,
    *,
    stochastic: bool = False,
    block_k: int = 256,
    block_n: int = 256,
) -> jax.Array:
    """Fused binarize (Eq. 1 or 2) + bitpack of a (K, N) master weight.

    Returns (ceil(K/32), N) int32. The stochastic path draws its uniform
    words with ``jax.random.bits``, so a key packs the same replica on
    every backend.
    """
    kdim, n = w.shape
    wp = pad_to_pack(w, axis=0)
    kp = _ceil_to(wp.shape[0], block_k)
    np_ = _ceil_to(n, block_n)
    if kp * np_ > 4 * max(kdim, 1) * max(n, 1):  # tiny: jnp reference
        if stochastic:
            if key is None:
                raise ValueError("stochastic binarization requires a key")
            bits = jax.random.bits(key, wp.shape, jnp.uint32)
            packed = ref.stoch_binarize_pack_ref(wp, bits)
        else:
            packed = ref.det_binarize_pack_ref(wp)
        return packed[:, :n]

    wpad = jnp.pad(wp, ((0, kp - wp.shape[0]), (0, np_ - n)))
    if stochastic:
        if key is None:
            raise ValueError("stochastic binarization requires a key")
        bits = jax.random.bits(key, wpad.shape, jnp.uint32)
        packed = binarize_pack_pallas(
            wpad, bits, stochastic=True, block_k=block_k, block_n=block_n,
            interpret=not _on_tpu())
    else:
        packed = binarize_pack_pallas(
            wpad, stochastic=False, block_k=block_k, block_n=block_n,
            interpret=not _on_tpu())
    return packed[: (kdim + PACK - 1) // PACK, :n]


def pack_master_weights(w: jax.Array) -> jax.Array:
    """Deterministic pack of an already-±1 tensor (serving path)."""
    return pack_bits(pad_to_pack(w, axis=0))
