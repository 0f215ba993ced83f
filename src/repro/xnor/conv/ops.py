"""Public jit'd wrappers for the XNOR conv engine.

Same contract as ``xnor/ops.py``: handle arbitrary static geometry (any
stride, SAME/VALID/explicit padding, ragged spatial dims, kh*kw*C not a
multiple of 32), pick interpret mode automatically off-TPU, and fall back to
the jnp oracles under ``use_pallas=False``. The popcount GEMM itself is the
existing ``xnor.ops.xnor_matmul_packed`` — this module only lowers conv onto
it: fused patch packing in front, exact zero-padding border correction
behind (see ``xnor.conv.packing`` for the correction math).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.compat import on_tpu as _on_tpu
from repro.xnor import ops as xops
from repro.xnor.conv import ref
from repro.xnor.conv.kernel import patch_pack_pallas
from repro.xnor.conv.packing import (border_correction, conv_epilogue,
                                     conv_geometry, conv_k, patch_words)


@functools.partial(jax.jit,
                   static_argnames=("ksize", "stride", "padding", "use_pallas"))
def sign_and_pack_patches(
    x: jax.Array,
    *,
    ksize,
    stride=(1, 1),
    padding="SAME",
    use_pallas: bool = True,
) -> jax.Array:
    """Fused sign-binarize + bitpack of im2col patches:
    (B, H, W, C) -> (B, OH, OW, kh*kw*ceil(C/32)) int32.

    The full-width activation never leaves the kernel unpacked; only the
    packed patch words are written back. Spatial zero padding and per-tap
    channel padding both carry sign bit 0 (see ``xnor.conv.packing``)."""
    _, h, w, _ = x.shape
    oh, ow, ((ph0, ph1), (pw0, pw1)) = conv_geometry(h, w, ksize, stride,
                                                     padding)
    if not use_pallas:
        return ref.sign_pack_patches_ref(x, ksize, stride, padding)
    xp = jnp.pad(x, ((0, 0), (ph0, ph1), (pw0, pw1), (0, 0)))
    return patch_pack_pallas(xp, ksize=ksize, stride=stride, oh=oh, ow=ow,
                             interpret=not _on_tpu())


def xnor_conv2d(
    x: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array | None = None,
    *,
    ksize,
    c_in: int,
    stride=(1, 1),
    padding="SAME",
    out_dtype=None,
    use_pallas: bool = True,
) -> jax.Array:
    """Fully-binary 2-D convolution, NHWC x (packed HWIO) -> NHWC.

    ``x`` is a real-valued (or already ±1) activation; ``w_packed`` is a
    ``pack_conv_kernel``-layout (kh*kw*ceil(c_in/32), N) int32 weight.
    Exactly equals ``conv(sign(x), sign(w))`` with zero padding (integers,
    no rounding — border pixels contribute 0, not -1), optionally times a
    per-output-channel ``scale``. ``out_dtype`` defaults to int32, or f32
    when a scale is applied."""
    return _xnor_conv2d(x, w_packed, scale, ksize=tuple(ksize), c_in=c_in,
                        stride=tuple(stride), padding=padding,
                        out_dtype=out_dtype, use_pallas=use_pallas)


@functools.partial(
    jax.jit, static_argnames=("ksize", "c_in", "stride", "padding",
                              "out_dtype", "use_pallas"))
def _xnor_conv2d(
    x: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array | None,
    *,
    ksize,
    c_in: int,
    stride,
    padding,
    out_dtype,
    use_pallas: bool,
) -> jax.Array:
    b, h, w, c = x.shape
    if c != c_in:
        raise ValueError(f"x has C={c}, packed kernel expects C={c_in}")
    if w_packed.shape[0] != patch_words(ksize, c_in):
        raise ValueError(
            f"w_packed has {w_packed.shape[0]} words, layout needs "
            f"{patch_words(ksize, c_in)} (k={ksize}, C={c_in})")
    n = w_packed.shape[-1]
    oh, ow, _ = conv_geometry(h, w, ksize, stride, padding)
    a = sign_and_pack_patches(x, ksize=ksize, stride=stride, padding=padding,
                              use_pallas=use_pallas)
    dot = xops.xnor_matmul_packed(a.reshape(b * oh * ow, -1), w_packed,
                                  None, k=conv_k(ksize, c_in),
                                  use_pallas=use_pallas,
                                  allow_extra_words=True)
    corr = border_correction(w_packed, h, w, ksize, stride, padding, c_in)
    return conv_epilogue(dot, corr, scale, out_dtype, b, oh, ow, n)
