"""Operations and bytes of one classifier forward, from logical shapes.

Each layer reads its f32 input activation once, writes its f32 output once
and reads its weights once; dense layers (f32 operands at the chip's
default matmul precision, one bf16 pass) count at the bf16 peak, ±1 x ±1
XNOR layers at the int8 peak with 1-bit weights in int32 words plus an f32
scale per output channel. ``model`` is the ``model`` dict of a
configuration file; ``layers()`` walks it in forward order.
"""
from __future__ import annotations

from bench.work.roofline import add, work

F32 = 4
PACK = 32


def layers(m: dict, batch: int):
    """Yields (name, kind, M, K, N, in_elems, out_elems, weight_bytes) per
    conv and FC layer; M counts output positions over the batch."""
    h, c = m["image_shape"][0], m["image_shape"][2]
    ci = 0
    for v in m["conv"]:
        if v == "M":
            h //= 2
            continue
        kind = m["conv_backends"][ci]
        k = 9 * c
        if kind == "xnor_conv":
            wbytes = 9 * -(-c // PACK) * v * 4 + v * 4
        else:
            wbytes = k * v * F32
        yield (f"conv/{ci}", kind, batch * h * h, k, v,
               batch * h * h * c, batch * h * h * v, wbytes)
        c = v
        ci += 1
    dims = [c * h * h] + list(m["fc"])
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        kind = m["fc_backends"][i]
        wbytes = (-(-a // PACK) * b * 4 + b * 4 if kind == "xnor"
                  else a * b * F32)
        yield (f"fc/{i}", kind, batch, a, b, batch * a, batch * b, wbytes)


def layer_work(kind: str, mm: int, k: int, n: int, in_elems: int,
               out_elems: int, wbytes: int) -> dict:
    cls = "int8" if kind in ("xnor", "xnor_conv") else "bf16"
    return work({cls: 2.0 * mm * k * n},
                (in_elems + out_elems) * F32 + wbytes)


def forward(m: dict, batch: int, kinds=None) -> dict:
    """Work of one forward (only the layers of ``kinds`` when given)."""
    return add(*[layer_work(kind, *rest)
                 for _, kind, *rest in layers(m, batch)
                 if kinds is None or kind in kinds])
