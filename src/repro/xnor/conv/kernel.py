"""Fused sign-binarize + bitpack of im2col patches, on the sign-pack kernel.

``patch_pack_pallas`` turns a zero-padded (B, Hp, Wp, C) activation into the
bitpacked im2col matrix (B, OH, OW, kh*kw*ceil(C/32)) int32. Only 1-bit
packed words leave the Pallas kernel: the full-width conv activation is read
once and never written back unpacked (the conv analogue of
``xnor.kernel.sign_pack_pallas``).

The per-tap word layout (see ``xnor.conv.packing``) is what makes this
cheap: channels pack per *pixel* once — word j of pixel (y, x) is the same in
every patch that covers that pixel — so the kernel packs every pixel's
channels to ``cw`` words (``sign_pack_rows`` over the (B*Hp*Wp, C) rows),
and the taps are then gathered from the packed (B, Hp, Wp, cw) words by
strided slices. The gather moves packed words only, 32x fewer bytes than
the f32 activation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.xnor.kernel import sign_pack_rows
from repro.xnor.packing import pad_features


def patch_pack_pallas(
    xp: jax.Array,
    *,
    ksize,
    stride=(1, 1),
    oh: int,
    ow: int,
    interpret: bool = False,
) -> jax.Array:
    """Fused im2col + sign + bitpack over an already spatially zero-padded
    (B, Hp, Wp, C) input (``ops.py`` computes the padding). Returns
    (B, OH, OW, kh*kw*ceil(C/32)) int32."""
    b, hp, wp, c = xp.shape
    kh, kw = ksize
    sh, sw = stride
    need = ((oh - 1) * sh + kh, (ow - 1) * sw + kw)
    if hp < need[0] or wp < need[1]:
        raise ValueError(
            f"padded image {(hp, wp)} too small for k={ksize} s={stride} "
            f"out={(oh, ow)} (needs {need})")
    rows = pad_features(xp.reshape(b * hp * wp, c))  # channel pad: bit 0
    words = sign_pack_rows(rows, interpret=interpret).reshape(b, hp, wp, -1)
    taps = [words[:, dy:dy + (oh - 1) * sh + 1:sh, dx:dx + (ow - 1) * sw + 1:sw]
            for dy in range(kh) for dx in range(kw)]
    return jnp.concatenate(taps, axis=-1)
