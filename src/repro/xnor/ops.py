"""Public jit'd wrappers around the XNOR-popcount Pallas kernels.

Same contract as ``kernels/ops.py``: handle arbitrary shapes (pad to block
multiples, slice back), flatten leading batch dims, pick interpret mode
automatically off-TPU, and fall back to the jnp oracles for shapes too small
to block. Padding everywhere uses 0-bits, which self-cancel in the popcount
formula (see ``xnor.packing``), so no output correction is ever needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.compat import ceil_to as _ceil_to, on_tpu as _on_tpu
from repro.core.packing import PACK
from repro.xnor import ref
from repro.xnor.kernel import lane_words, sign_pack_rows, xnor_matmul_pallas
from repro.xnor.packing import pad_features


@functools.partial(jax.jit, static_argnames=("block_m", "block_k", "use_pallas"))
def sign_and_pack(
    x: jax.Array,
    *,
    block_m: int = 128,
    block_k: int = 512,
    use_pallas: bool = True,
) -> jax.Array:
    """Fused sign-binarize (Eq. 1) + bitpack: ``(..., K) -> (..., ceil(K/32))``.

    The full-width activation never leaves the kernel unpacked; only the
    packed int32 words are written back (16x fewer bytes than bf16)."""
    *lead, kdim = x.shape
    k32 = (kdim + PACK - 1) // PACK
    x2 = pad_features(x.reshape(-1, kdim))
    m = x2.shape[0]
    if not use_pallas or m * kdim < block_m * block_k:
        return ref.sign_pack_ref(x2).reshape(*lead, k32)
    packed = sign_pack_rows(x2, block_m=block_m, block_k=block_k,
                            interpret=not _on_tpu())
    return packed.reshape(*lead, k32)


def xnor_matmul_packed(
    a_packed: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array | None = None,
    *,
    k: int,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    out_dtype=None,
    use_pallas: bool = True,
    allow_extra_words: bool = False,
) -> jax.Array:
    """Popcount matmul over pre-packed operands: a (..., K32), w (K32, N).

    ``k`` is the true contraction length (static). ``allow_extra_words``
    permits K32 > ceil(k/32), for layouts whose surplus positions are 0-bit
    on both operand sides and so self-cancel in the popcount (the conv
    engine's per-tap channel padding); leave it off for the plain FC layout,
    where a word-count mismatch is always a caller bug."""
    return _xnor_matmul_packed(a_packed, w_packed, scale, k=k,
                               block_m=block_m, block_n=block_n,
                               block_k=block_k, out_dtype=out_dtype,
                               use_pallas=use_pallas,
                               allow_extra_words=allow_extra_words)


@functools.partial(
    jax.jit, static_argnames=("k", "block_m", "block_n", "block_k",
                              "out_dtype", "use_pallas", "allow_extra_words"))
def _xnor_matmul_packed(
    a_packed: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array | None = None,
    *,
    k: int,
    block_m: int,
    block_n: int,
    block_k: int,
    out_dtype,
    use_pallas: bool,
    allow_extra_words: bool = False,
) -> jax.Array:
    *lead, k32 = a_packed.shape
    k32w, n = w_packed.shape
    if k32 != k32w:
        raise ValueError(f"packed K mismatch: a has {k32} words, w has {k32w}")
    needed = (k + PACK - 1) // PACK
    if (k32 < needed) if allow_extra_words else (k32 != needed):
        raise ValueError(f"k={k} inconsistent with {k32} packed words")
    a2 = a_packed.reshape(-1, k32)
    m = a2.shape[0]
    if not use_pallas or m * n * k < block_m * block_n * block_k:
        out = ref.xnor_matmul_ref(a2, w_packed, k, scale, out_dtype=out_dtype)
        return out.reshape(*lead, n)

    bm = min(block_m, _ceil_to(m, 8))
    bk32 = lane_words(k32, block_k // PACK)
    mp, np_, kp32 = _ceil_to(m, bm), _ceil_to(n, block_n), _ceil_to(k32, bk32)
    ap = jnp.pad(a2, ((0, mp - m), (0, kp32 - k32)))
    wp = jnp.pad(w_packed, ((0, kp32 - k32), (0, np_ - n)))
    sp = None if scale is None else jnp.pad(scale, (0, np_ - n))
    out = xnor_matmul_pallas(
        ap, wp, sp, k_total=k,
        block_m=bm, block_n=block_n, block_k=bk32 * PACK,
        out_dtype=out_dtype, interpret=not _on_tpu(),
    )
    return out[:m, :n].reshape(*lead, n)


def xnor_matmul(
    x: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array | None = None,
    *,
    k: int | None = None,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    out_dtype=None,
    use_pallas: bool = True,
) -> jax.Array:
    """End-to-end fully-binary linear: sign->pack ``x``, then popcount matmul.

    ``x`` is a real-valued (or already ±1) activation of shape (..., K);
    ``w_packed`` is a ``core.packing``-layout (ceil(K/32), N) int32 weight.
    Exactly equals ``sign(x) @ sign(w)`` (integers, no rounding)."""
    kdim = k if k is not None else x.shape[-1]
    if x.shape[-1] != kdim:
        raise ValueError(f"x K={x.shape[-1]} != declared k={kdim}")
    a = sign_and_pack(x, block_m=block_m, block_k=block_k,
                      use_pallas=use_pallas)
    return xnor_matmul_packed(a, w_packed, scale, k=kdim,
                              block_m=block_m, block_n=block_n,
                              block_k=block_k, out_dtype=out_dtype,
                              use_pallas=use_pallas)
