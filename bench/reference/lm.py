"""Plain float32 reference of the binarized decoder LM (det plan).

Independent of the program: it draws the configuration's weights from the
seed itself (the serving draw: ``fan_in**-0.5 * normal`` in float32, held in
the configuration's dtype), binarizes every projection as Eq. (1) does (``w > 0 -> +1``,
else -1) times its per-output-channel mean |w|, and runs a full causal
forward over whole sequences in float32 with every matmul at
``Precision.HIGHEST``. One layer's weights exist at a time, so the
reference fits beside nothing else on the chip.

``quant`` switches the same forward to a lower precision for the control:
the residual stream after every block and every matmul operand (K/V and the
attention probabilities included) are rounded per row to ``int8``
(absmax / 127) or ``fp8`` (float8_e4m3fn, absmax / 448); norms, softmax and
accumulation stay float32.

Model keys are those of a configuration file's ``model`` dict.
"""
from __future__ import annotations

import json

import numpy as np

HI = None  # set on first use: jax.lax.Precision.HIGHEST


def _jax():
    import jax
    import jax.numpy as jnp

    global HI
    HI = jax.lax.Precision.HIGHEST
    return jax, jnp


def quantize(x, quant, axis):
    """``x`` rounded to ``quant`` per slice along ``axis`` (None: as is)."""
    jax, jnp = _jax()
    if quant is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if quant == "int8":
        s = jnp.where(amax > 0, amax / 127.0, 1.0)
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if quant == "fp8":
        s = jnp.where(amax > 0, amax / 448.0, 1.0)
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown control precision {quant!r}")


def mm(a, b, quant=None):
    """a (..., K) @ b (K, N) at HIGHEST, operands rounded by ``quant``."""
    jax, jnp = _jax()
    a = quantize(a, quant, -1)
    b = quantize(b, quant, 0)
    return jnp.matmul(a, b, precision=HI)


def _draw(key, shape, fan_in, dtype):
    """The serving draw of one weight: float32 normal, held in ``dtype``."""
    jax, jnp = _jax()
    w = fan_in ** -0.5 * jax.random.normal(key, shape, jnp.float32)
    return w.astype(dtype).astype(jnp.float32)


def binarize(w):
    """Eq. (1) signs times the per-output-channel mean |w| over K."""
    jax, jnp = _jax()
    alpha = jnp.mean(jnp.abs(w), axis=0)
    return jnp.where(w > 0, 1.0, -1.0) * alpha


def rms_norm(x, eps=1e-6):
    jax, jnp = _jax()
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, pos, theta):
    """x (B, S, H, hd), pos (S,): rotate the two halves of each head."""
    jax, jnp = _jax()
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * freqs          # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def gelu(x):
    """tanh-approximated GELU (the published ``gelu_pytorch_tanh``)."""
    jax, jnp = _jax()
    c = (2.0 / np.pi) ** 0.5
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x * x * x)))


def attention(m, q, k, v, quant=None, block=512):
    """Causal grouped attention, one block of queries at a time.
    q (B, S, H, hd); k, v (B, S, KV, hd)."""
    jax, jnp = _jax()
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, hd)
    outs = []
    for s0 in range(0, s, block):
        n = min(block, s - s0)
        kk, vv = k[:, : s0 + n], v[:, : s0 + n]
        qb = qg[:, s0:s0 + n]
        lg = jnp.einsum("bqngd,bsnd->bngqs", quantize(qb, quant, -1),
                        quantize(kk, quant, -1), precision=HI) * hd ** -0.5
        mask = (jnp.arange(s0 + n)[None, :]
                <= (s0 + jnp.arange(n))[:, None])
        lg = jnp.where(mask, lg, -jnp.inf)
        p = jax.nn.softmax(lg, axis=-1)
        o = jnp.einsum("bngqs,bsnd->bqngd", quantize(p, quant, -1),
                       quantize(vv, quant, 1), precision=HI)
        outs.append(o.reshape(b, n, h * hd))
    return jnp.concatenate(outs, axis=1)


_PROGRAMS: dict = {}


def _programs(m: dict):
    """The jitted layer, embedding and head of one model, built once per
    model and taking their keys as arguments (so one compiled program serves
    every seed)."""
    tag = json.dumps(m, sort_keys=True)
    if tag in _PROGRAMS:
        return _PROGRAMS[tag]
    jax, jnp = _jax()
    d, hd = m["d_model"], m["head_dim"]
    h, kvh = m["n_heads"], m["n_kv_heads"]
    q_dim, kv_dim = h * hd, kvh * hd
    glu = m.get("mlp_type", "glu") == "glu"
    dt = jnp.dtype(m.get("dtype", "float32"))

    def draw(key, shape, fan_in):
        return _draw(key, shape, fan_in, dt)

    def layer(x, attn_key, mlp_key, quant):
        b, s, _ = x.shape
        k1, k2 = jax.random.split(attn_key)
        w_qkv = binarize(draw(k1, (d, q_dim + 2 * kv_dim), d))
        w_o = binarize(draw(k2, (q_dim, d), q_dim))
        pos = jnp.arange(s)
        y = mm(rms_norm(x), w_qkv, quant)
        q = y[..., :q_dim].reshape(b, s, h, hd)
        k = y[..., q_dim:q_dim + kv_dim].reshape(b, s, kvh, hd)
        v = y[..., q_dim + kv_dim:].reshape(b, s, kvh, hd)
        q = rope(q, pos, m["rope_theta"])
        k = rope(k, pos, m["rope_theta"])
        x = quantize(x + mm(attention(m, q, k, v, quant), w_o, quant),
                     quant, -1)
        hn = rms_norm(x)
        if glu:
            ka, kb, kc = jax.random.split(mlp_key, 3)
            wg = binarize(draw(ka, (d, m["d_ff"]), d))
            wu = binarize(draw(kb, (d, m["d_ff"]), d))
            wd = binarize(draw(kc, (m["d_ff"], d), m["d_ff"]))
            f = jax.nn.silu(mm(hn, wg, quant)) * mm(hn, wu, quant)
            return quantize(x + mm(f, wd, quant), quant, -1)
        ka, kb = jax.random.split(mlp_key)
        wi = binarize(draw(ka, (d, m["d_ff"]), d))
        wo = binarize(draw(kb, (m["d_ff"], d), m["d_ff"]))
        return quantize(x + mm(gelu(mm(hn, wi, quant)), wo, quant),
                        quant, -1)

    def embed(tokens, key, quant):
        table = draw(key, (m["vocab_size"], d), d)
        return quantize(jnp.take(table, tokens, axis=0), quant, -1)

    def head(x, rows, key, quant):
        if m.get("tie_embeddings"):
            w = draw(key, (m["vocab_size"], d), d).T
        else:
            w = draw(key, (d, m["vocab_size"]), d)
        xr = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        return mm(rms_norm(xr), w, quant)

    progs = (jax.jit(layer, static_argnums=3),
             jax.jit(embed, static_argnums=2),
             jax.jit(head, static_argnums=3))
    _PROGRAMS[tag] = progs
    return progs


class LmReference:
    """The reference model of one seed. ``logits(seqs, rows, quant)`` runs
    the whole stack over ``seqs`` (B, S) int32 and returns float32 logits of
    the positions ``rows`` (B, R) index."""

    def __init__(self, model: dict, key):
        jax, jnp = _jax()
        if model.get("sliding_window"):
            raise NotImplementedError(
                "the reference computes full causal attention; a sliding "
                "window needs contexts kept inside it")
        self.m = model
        self.keys = jax.random.split(key, 8)
        n = model["n_layers"]
        self.attn_keys = jax.random.split(self.keys[2], n)
        self.mlp_keys = jax.random.split(self.keys[3], n)

    def hidden(self, seqs, quant=None):
        import jax.numpy as jnp

        layer, embed, _ = _programs(self.m)
        x = embed(jnp.asarray(seqs, jnp.int32), self.keys[0], quant)
        for i in range(self.m["n_layers"]):
            x = layer(x, self.attn_keys[i], self.mlp_keys[i], quant)
        return x

    def logits(self, seqs, rows, quant=None):
        """Device array (B, R, V) of float32 logits."""
        import jax.numpy as jnp

        _, _, head = _programs(self.m)
        key = self.keys[0] if self.m.get("tie_embeddings") else self.keys[1]
        return head(self.hidden(seqs, quant), jnp.asarray(rows, jnp.int32),
                    key, quant)
