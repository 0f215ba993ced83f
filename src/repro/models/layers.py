"""Base layers: linear application (dense or bitpacked-binary), norms,
embeddings, rotary position embeddings, initializers.

Models are *binarization-agnostic*: ``train_step`` binarizes the master
parameter tree (Alg. 1) before calling the forward pass, and the serving path
may substitute :class:`PackedLinear` leaves (bitpacked binary weights +
optional per-channel scale), :class:`XnorLinear` / :class:`XnorConv` leaves
(binary weights *and* binary activations, XNOR-popcount compute), or any
other serving leaf registered with ``repro.engine``. ``apply_linear`` and
``apply_conv2d`` dispatch through the backend registry on the leaf type, so
the same model code serves every datapath — which backend each layer gets is
decided (and recorded) by ``repro.engine.compile_plan``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedLinear:
    """Bitpacked binary weight: ``unpack(packed) * scale`` of shape (K, N)."""

    packed: jax.Array               # (K // 32, N) int32
    scale: jax.Array | None         # (N,) f32 or None
    k: int                          # static original K
    # static: which master dim is split over a mesh's "model" axis — "n"
    # (out-channel), "k" (contraction words) or None — set by placement
    # (distributed.sharding.place_packed_params)
    tp: str | None = None

    def tree_flatten(self):
        return (self.packed, self.scale), (self.k, self.tp)

    @classmethod
    def tree_unflatten(cls, aux, children):
        packed, scale = children
        return cls(packed, scale, *aux)

    @property
    def shape(self):
        return (self.k, self.packed.shape[-1])

    @property
    def master_shape(self):
        """True master-weight shape incl. leading stack dims (L/E, K, N) —
        the dense-baseline shape for byte accounting, independent of any
        pad words the packed layout carries."""
        return tuple(self.packed.shape[:-2]) + (self.k, self.packed.shape[-1])

    @property
    def ndim(self):
        return 2


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class XnorLinear:
    """Fully-binary linear: weights bitpacked like :class:`PackedLinear`, and
    *activations* sign-binarized + bitpacked on the fly, so the dot product is
    integer XNOR-popcount (``repro.xnor``) — no MXU, no full-width activation
    traffic."""

    packed: jax.Array               # (K // 32, N) int32
    scale: jax.Array | None         # (N,) f32 or None
    k: int                          # static original K
    # static: which master dim is split over a mesh's "model" axis — "n"
    # (out-channel), "k" (contraction words) or None — set by placement
    # (distributed.sharding.place_packed_params)
    tp: str | None = None

    def tree_flatten(self):
        return (self.packed, self.scale), (self.k, self.tp)

    @classmethod
    def tree_unflatten(cls, aux, children):
        packed, scale = children
        return cls(packed, scale, *aux)

    @property
    def shape(self):
        return (self.k, self.packed.shape[-1])

    @property
    def master_shape(self):
        """True master-weight shape incl. leading stack dims (see
        :class:`PackedLinear`). The packed array may legally hold more
        words than ceil(K/32) (self-cancelling pad layouts); this never
        reflects them."""
        return tuple(self.packed.shape[:-2]) + (self.k, self.packed.shape[-1])

    @property
    def ndim(self):
        return 2


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class XnorConv:
    """Fully-binary 2-D convolution leaf: the (kh, kw, C, N) kernel is
    bitpacked along the flattened kh*kw*C contraction axis (per-tap word
    layout, ``repro.xnor.conv``), and at apply time the input activation is
    sign-binarized + bitpacked into im2col patches on the fly, so the conv
    is an integer XNOR-popcount GEMM — no MXU, 1-bit activation traffic."""

    packed: jax.Array               # (kh*kw*ceil(c_in/32), N) int32
    scale: jax.Array | None         # (N,) f32 or None
    ksize: tuple[int, int]          # static (kh, kw)
    c_in: int                       # static input channels

    def tree_flatten(self):
        return (self.packed, self.scale), (self.ksize, self.c_in)

    @classmethod
    def tree_unflatten(cls, aux, children):
        packed, scale = children
        return cls(packed, scale, aux[0], aux[1])

    @property
    def k(self):
        """True contraction length kh*kw*c_in."""
        return self.ksize[0] * self.ksize[1] * self.c_in

    @property
    def shape(self):
        return (*self.ksize, self.c_in, self.packed.shape[-1])

    @property
    def master_shape(self):
        """True (kh, kw, C, N) master shape. The packed words cover
        kh*kw*ceil(C/32)*32 >= kh*kw*C positions (per-tap channel padding);
        dense-baseline accounting must use the true C recorded here."""
        return self.shape

    @property
    def ndim(self):
        return 4


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedConv:
    """Bitpacked *binary-weight* 2-D convolution leaf with real-valued
    activations: the (kh, kw, C, N) kernel is binarized and bitpacked along
    the flattened kh*kw*C contraction axis (flat FC word layout,
    ceil(kh*kw*C/32) words per output channel), and at apply time the words
    unpack back to ±1 [* alpha] and run through the ordinary dense conv —
    ``binarized_dense`` numerics at 1-bit weight storage. This is what makes
    K-replica stochastic ensembles (``repro.stoch``) affordable for conv
    nets: K packed conv replicas cost ~K/16 of one bf16 kernel."""

    packed: jax.Array               # (ceil(kh*kw*c_in/32), N) int32
    scale: jax.Array | None         # (N,) f32 or None
    ksize: tuple[int, int]          # static (kh, kw)
    c_in: int                       # static input channels

    def tree_flatten(self):
        return (self.packed, self.scale), (self.ksize, self.c_in)

    @classmethod
    def tree_unflatten(cls, aux, children):
        packed, scale = children
        return cls(packed, scale, aux[0], aux[1])

    @property
    def k(self):
        """True contraction length kh*kw*c_in."""
        return self.ksize[0] * self.ksize[1] * self.c_in

    @property
    def shape(self):
        return (*self.ksize, self.c_in, self.packed.shape[-1])

    @property
    def master_shape(self):
        """True (kh, kw, C, N) master shape; the flat packed layout may pad
        the last word (ceil), dense-baseline accounting uses the true K."""
        return self.shape

    @property
    def ndim(self):
        return 4


def apply_linear(w, x: jax.Array, bias: jax.Array | None = None, *,
                 sh=None, kind: str | None = None) -> jax.Array:
    """x @ w (+ bias). The leaf type of ``w`` selects its backend through
    the ``repro.engine`` registry (dense array, PackedLinear, XnorLinear, or
    any user-registered serving leaf) — no isinstance chain here.

    ``sh``/``kind`` thread the activation-sharding context
    (``repro.distributed.sharding.ShardCtx``) through the dispatch seam:
    the constraint lands on the backend's *output* regardless of which
    datapath served the layer, so packed / xnor leaves inherit exactly the
    TP layout the dense path would produce. No-op when ``sh`` is None (or
    built with ``mesh=None``)."""
    from repro.engine import registry

    out = registry.apply_linear(w, x)
    if bias is not None:
        out = out + bias.astype(out.dtype)
    if sh is not None and kind is not None:
        out = sh.act(out, kind)
    return out


def apply_conv2d(w, x: jax.Array, bias: jax.Array | None = None, *,
                 stride=(1, 1), padding="SAME", sh=None,
                 kind: str | None = None) -> jax.Array:
    """conv2d(x, w) (+ bias) in NHWC/HWIO. The leaf type of ``w`` selects
    its backend through the ``repro.engine`` registry (dense / binarized-
    dense kernels, XnorConv, or any user-registered serving leaf).
    ``sh``/``kind`` constrain the output like :func:`apply_linear`."""
    from repro.engine import registry

    out = registry.apply_conv2d(w, x, stride=stride, padding=padding)
    if bias is not None:
        out = out + bias.astype(out.dtype)
    if sh is not None and kind is not None:
        out = sh.act(out, kind)
    return out


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def he_normal(key, shape, dtype=jnp.float32, fan_in=None):
    """He initialization (the paper's choice for FC/VGG nets)."""
    fan_in = fan_in if fan_in is not None else shape[0]
    std = (2.0 / max(fan_in, 1)) ** 0.5
    return std * jax.random.normal(key, shape, dtype)


def lm_init(key, shape, dtype=jnp.float32, fan_in=None):
    """Scaled-normal init for transformer projections."""
    fan_in = fan_in if fan_in is not None else shape[-2] if len(shape) >= 2 else shape[0]
    std = fan_in ** -0.5
    return std * jax.random.normal(key, shape, dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def batch_norm(x, scale, bias, mean, var, *, training: bool,
               momentum: float = 0.9, eps: float = 1e-5, axes=(0,)):
    """BatchNorm with running stats (the paper normalizes every layer output).

    Returns (y, new_mean, new_var); in eval mode the stats pass through."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    if training:
        mu = jnp.mean(x32, axis=axes)
        va = jnp.var(x32, axis=axes)
        new_mean = momentum * mean + (1.0 - momentum) * mu
        new_var = momentum * var + (1.0 - momentum) * va
    else:
        mu, va = mean, var
        new_mean, new_var = mean, var
    shape = [1] * x.ndim
    shape[-1] = x.shape[-1]
    y = (x32 - mu.reshape(shape)) * jax.lax.rsqrt(va.reshape(shape) + eps)
    y = y * scale.reshape(shape) + bias.reshape(shape)
    return y.astype(dt), new_mean, new_var


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                      # (hd/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                      # (B, S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# embedding
# ---------------------------------------------------------------------------

def embed_lookup(embedding: jax.Array, tokens: jax.Array, dtype) -> jax.Array:
    return jnp.take(embedding, tokens, axis=0).astype(dtype)
