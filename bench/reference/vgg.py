"""Plain float32 reference of the binarized VGG-16 classifier (xnor plan).

Independent of the program: it draws the configuration's weights from the
seed itself (He-normal float32, one key per conv then per FC layer, as the
configuration's initializer does), binarizes as Eq. (1) (``w > 0 -> +1``)
with the per-output-channel mean |w|, and runs the forward with
``lax.conv`` / ``matmul`` at ``Precision.HIGHEST``:

* ``xnor_conv`` / ``xnor`` layers: conv (or matmul) of sign(activation) and
  sign(weight) with zero SAME padding, times the scale; exact integers;
* ``binarized_dense``: the ±scale weight on the real activation;
* ``dense``: the float32 weight on the real activation;
* eval-mode batch norm at its initial statistics, then sign on the
  activations that feed binary layers and ReLU on the others.

The chip runs float32 matmuls at its default precision, one bfloat16 pass
with float32 accumulation; ``dense_operands: "bfloat16"`` in the model
rounds the operands of real-valued layers to bfloat16 as that pass does.
``quant`` (the control) rounds them further, per image and per output
channel, to int8 or fp8 (see ``bench.reference.lm.quantize``).
"""
from __future__ import annotations

import json

from bench.reference.lm import quantize

_PROGRAMS: dict = {}


def init(m: dict, key):
    """([conv kernels (3,3,C,N)], [fc kernels (K,N)]) in float32."""
    import jax
    import jax.numpy as jnp

    keys = iter(jax.random.split(key, 32))
    convs, fcs = [], []
    c = m["image_shape"][2]
    for v in m["conv"]:
        if v == "M":
            continue
        std = (2.0 / (9 * c)) ** 0.5
        convs.append(std * jax.random.normal(next(keys), (3, 3, c, v),
                                             jnp.float32))
        c = v
    dims = [c] + list(m["fc"])
    for a, b in zip(dims[:-1], dims[1:]):
        std = (2.0 / a) ** 0.5
        fcs.append(std * jax.random.normal(next(keys), (a, b), jnp.float32))
    return convs, fcs


def _forward(m: dict, key, x, quant):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    convs, fcs = init(m, key)
    eps = m.get("bn_eps", 1e-5)

    def real(a, axis):
        if m.get("dense_operands") == "bfloat16":
            a = a.astype(jnp.bfloat16).astype(jnp.float32)
        return quantize(a, quant, axis)

    def sign(a):
        return jnp.where(a > 0, 1.0, -1.0)

    def conv(a, w):
        return jax.lax.conv_general_dilated(
            a, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=hi)

    def bn(a):
        return a * jax.lax.rsqrt(jnp.float32(1.0) + eps)

    n_conv = len(convs)
    ci = 0
    for v in m["conv"]:
        if v == "M":
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
            continue
        w = convs[ci]
        kind = m["conv_backends"][ci]
        alpha = jnp.mean(jnp.abs(w), axis=(0, 1, 2))
        if kind == "xnor_conv":
            y = conv(sign(x), sign(w)) * alpha
        elif kind == "binarized_dense":
            y = conv(real(x, (1, 2, 3)), real(sign(w) * alpha, (0, 1, 2)))
        else:
            y = conv(real(x, (1, 2, 3)), real(w, (0, 1, 2)))
        y = bn(y)
        x = sign(y) if 1 <= ci < n_conv - 1 else jax.nn.relu(y)
        ci += 1
    x = x.reshape(x.shape[0], -1)
    for i, w in enumerate(fcs):
        if m["fc_backends"][i] == "xnor":
            alpha = jnp.mean(jnp.abs(w), axis=0)
            y = jnp.matmul(sign(x), sign(w), precision=hi) * alpha
        else:
            y = jnp.matmul(real(x, -1), real(w, 0), precision=hi)
        y = bn(y)
        x = sign(y) if i < len(fcs) - 1 else y
    return x


def logits(m: dict, key, images, quant=None):
    """Float32 logits (B, classes) of ``images`` (B, H, W, C)."""
    import jax

    tag = json.dumps(m, sort_keys=True)
    if tag not in _PROGRAMS:
        _PROGRAMS[tag] = jax.jit(
            lambda k, x, q: _forward(m, k, x, q), static_argnums=2)
    return _PROGRAMS[tag](key, images, quant)
