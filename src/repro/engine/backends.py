"""Built-in layer backends: dense, packed, xnor, xnor_conv, binarized_dense.

Each backend bundles the eligibility rule, pack transform, apply
implementation and cost model for one datapath and registers itself with
``repro.engine.registry``. The pack transforms are bit-for-bit the ones the
legacy ``serve.engine.pack_params`` monolith applied (same PRNG key folding
by leaf index, same scale axes), so a compiled plan packs a tree into
exactly the pytree the old code produced.

Priority order (highest wins among eligible):

  xnor_conv (40) > xnor (30) > packed (20) > packed_conv (15)
    > binarized_dense (10) > dense (0)

To add backend N+1, write these four functions and call
``register_backend`` — no edits to models/layers, serve/engine or the plan
compiler are needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import binarize as B
from repro.core.binarize import BinarizeMode
from repro.core.packing import PACK
from repro.engine import costs
from repro.engine.registry import (BackendSpec, LeafContext, PackContext,
                                   register_backend)
from repro.models.layers import (PackedConv, PackedLinear, XnorConv,
                                 XnorLinear)


# ---------------------------------------------------------------------------
# eligibility predicates
# ---------------------------------------------------------------------------

def _dense_eligible(lc: LeafContext) -> tuple[bool, str]:
    return True, "ok"


def _packable(lc: LeafContext) -> tuple[bool, str]:
    """Shared gate for the bitpacked-weight matmul backends."""
    if not lc.selected:
        return False, "policy-excluded"
    if lc.is_conv:
        return False, "conv kernel (no packed-weight MXU conv lowering)"
    if lc.ndim < 2:
        return False, f"ndim={lc.ndim} < 2 (not matmul-shaped)"
    if lc.shape[-2] % PACK != 0:
        return False, f"K={lc.shape[-2]} % {PACK} != 0"
    return True, "ok"


def _xnor_gate(lc: LeafContext) -> tuple[bool, str]:
    """Shared mode/activation-policy gate for the fully-binary backends."""
    if lc.mode != "xnor":
        return False, f"mode={lc.mode} != xnor"
    if not lc.xnor_selected:
        return False, ("xnor-policy-excluded (real-valued-input boundary)"
                       if lc.xnor_boundary else "xnor-policy-excluded")
    return True, "ok"


def _xnor_eligible(lc: LeafContext) -> tuple[bool, str]:
    ok, why = _packable(lc)
    if not ok:
        return ok, why
    return _xnor_gate(lc)


def _conv_selected(lc: LeafContext) -> tuple[bool, str]:
    if not lc.is_conv:
        return False, "not a conv-stack kernel"
    if not lc.selected:
        return False, "policy-excluded"
    return True, "ok"


def _xnor_conv_eligible(lc: LeafContext) -> tuple[bool, str]:
    ok, why = _conv_selected(lc)
    if not ok:
        return ok, why
    return _xnor_gate(lc)


def _packed_conv_eligible(lc: LeafContext) -> tuple[bool, str]:
    """Bitpacked conv weights, stoch mode only: in det/xnor mode the dense
    binarized_dense fallback costs the same bytes per single sample, but a
    K-replica stochastic ensemble (repro.stoch) needs 1-bit storage so K
    replicas stay ~K/16 of one bf16 kernel."""
    ok, why = _conv_selected(lc)
    if not ok:
        return ok, why
    if lc.mode != "stoch":
        return False, f"mode={lc.mode} != stoch (dense ±1 fallback is free)"
    return True, "ok"


# ---------------------------------------------------------------------------
# pack transforms (bit-identical to the legacy pack_params monolith)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=1)
def _abs_mean(w, axis):
    """f32 mean |w| over ``axis`` (the BWN alpha). One jit, so a bf16
    master is never copied to f32 in full."""
    return jnp.mean(jnp.abs(w.astype(jnp.float32)), axis=axis)


def _pack_dense(lc: LeafContext, leaf, pc: PackContext):
    return leaf


def _missing_key_error(lc: LeafContext) -> ValueError:
    """Actionable 'no PRNG key' error naming the exact leaf that failed."""
    return ValueError(
        f"stochastic packing requires a PRNG key, but none was supplied "
        f"for leaf {lc.path!r} (leaf index {lc.index}): pass "
        f"key=jax.random.key(seed) to plan.pack(...) / pack_params(...), "
        f"or compile the plan with mode='det' for keyless deterministic "
        f"binarization")


def _binarize_values(lc: LeafContext, leaf, pc: PackContext):
    if pc.weight_mode is BinarizeMode.STOCHASTIC:
        if pc.key is None:
            raise _missing_key_error(lc)
        return B.stochastic_binarize(leaf, jax.random.fold_in(pc.key, lc.index))
    return B.deterministic_binarize(leaf)


def _pack_binarized_dense(lc: LeafContext, leaf, pc: PackContext):
    """Binarized values (±1 [* alpha]) kept in dense array form — the Alg.-1
    inference network for conv layers with no bitpacked lowering."""
    scale = None
    if pc.with_scale:
        scale = _abs_mean(leaf, (0, 1, 2))
    wb = _binarize_values(lc, leaf, pc)
    if scale is not None:
        wb = (wb.astype(jnp.float32) * scale).astype(leaf.dtype)
    return wb


def _pack_linear(cls, lc: LeafContext, leaf, pc: PackContext):
    """Binarize + bitpack a (..., K, N) projection into ``cls``. Stacked
    leaves (L, K, N) pack per layer via vmap so ``lax.scan`` slices the
    result exactly like dense leaves."""
    from repro.kernels import ops as kops

    k_dim, n_dim = leaf.shape[-2], leaf.shape[-1]
    lead = leaf.shape[:-2]
    w2 = leaf.reshape((-1, k_dim, n_dim))
    if pc.weight_mode is BinarizeMode.STOCHASTIC:
        if pc.key is None:
            raise _missing_key_error(lc)
        ks = jax.random.split(jax.random.fold_in(pc.key, lc.index),
                              w2.shape[0])
        packed = jax.vmap(
            lambda w, kk: kops.binarize_and_pack(w, kk, stochastic=True)
        )(w2, ks)
    else:
        packed = jax.vmap(
            lambda w: kops.binarize_and_pack(w, stochastic=False))(w2)
    scale = None
    if pc.with_scale:
        scale = _abs_mean(w2, 1)  # (-1, N)
        scale = scale.reshape(lead + (n_dim,))
    packed = packed.reshape(lead + (k_dim // PACK, n_dim))
    return cls(packed, scale, k_dim)


def _pack_packed_conv(lc: LeafContext, leaf, pc: PackContext):
    """Binarize + bitpack a (kh, kw, C, N) conv kernel along the flattened
    kh*kw*C axis (flat FC word layout; ops.py pads the ragged last word
    with self-cancelling +1/-1 pairs, and apply slices back to the true K).
    Stoch-mode only, so the key is mandatory."""
    from repro.kernels import ops as kops

    if pc.key is None:
        raise _missing_key_error(lc)
    kh, kw, c_in, n_dim = leaf.shape
    scale = None
    if pc.with_scale:
        scale = _abs_mean(leaf, (0, 1, 2))
    w2 = leaf.reshape((kh * kw * c_in, n_dim))
    packed = kops.binarize_and_pack(
        w2, jax.random.fold_in(pc.key, lc.index), stochastic=True)
    return PackedConv(packed, scale, (kh, kw), c_in)


def _pack_xnor_conv(lc: LeafContext, leaf, pc: PackContext):
    from repro.xnor.conv import pack_conv_kernel

    scale = None
    if pc.with_scale:
        scale = _abs_mean(leaf, (0, 1, 2))
    kh, kw, c_in, _ = leaf.shape
    return XnorConv(pack_conv_kernel(leaf), scale, (kh, kw), c_in)


# ---------------------------------------------------------------------------
# apply implementations
# ---------------------------------------------------------------------------

def _apply_dense(w, x, *, stride=None, padding=None):
    if stride is None:
        return jnp.dot(x, w.astype(x.dtype))
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), window_strides=stride, padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _ambient_mesh():
    """The mesh of ``jax.set_mesh`` when it spans several devices, else
    None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty or mesh.size == 1 else mesh


def _per_device(run, w, x):
    """``run(x, packed, scale)`` — a Pallas kernel — under the ambient mesh.

    The TPU compiler cannot partition a Pallas kernel, so on a mesh of
    several devices it runs once per device under ``shard_map``, on the
    weight slice placement gave that device (``w.tp``): the local
    out-channels for "n", the local contraction words for "k" (``run``
    then reduces over "model" itself), everything for None. The leading
    (batch) dim of ``x`` stays split over the data axes where it divides.
    Each output element is computed exactly as on one device."""
    mesh = _ambient_mesh()
    if mesh is None:
        return run(x, w.packed, w.scale)
    from jax.sharding import PartitionSpec as P

    split = w.tp if "model" in mesh.axis_names else None
    k_ax = "model" if split == "k" else None
    n_ax = "model" if split == "n" else None
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    batch = None
    if dp and x.ndim > 1 and x.shape[0] % n_dp == 0:
        batch = dp if len(dp) > 1 else dp[0]
    lead = (batch,) + (None,) * (x.ndim - 2) if x.ndim > 1 else ()
    specs = [P(*lead, k_ax),
             P(*(None,) * (w.packed.ndim - 2), k_ax, n_ax)]
    args = [x, w.packed]
    if w.scale is not None:
        specs.append(P(*(None,) * (w.scale.ndim - 1), n_ax))
        args.append(w.scale)
    return jax.shard_map(
        lambda x, packed, scale=None: run(x, packed, scale), mesh=None,
        in_specs=tuple(specs), out_specs=P(*lead, n_ax),
        check_vma=False)(*args)


def _apply_packed(w: PackedLinear, x):
    from repro.kernels import ops

    def run(x, packed, scale):
        return ops.binary_matmul(x, packed, scale, out_dtype=jnp.float32)

    return _per_device(run, w, x).astype(x.dtype)


def _apply_xnor(w: XnorLinear, x):
    from repro.xnor import ops as xops

    if w.tp != "k" or _ambient_mesh() is None:
        def run(x, packed, scale):
            return xops.xnor_matmul(x, packed, scale, k=w.k,
                                    out_dtype=jnp.float32)

        return _per_device(run, w, x).astype(x.dtype)

    # Row-parallel: each device dots its contraction words; the int32
    # partial dots sum exactly over "model". Zero-padding x to whole words
    # adds 0-bit pairs, each +1 in the summed dot, taken off after.
    from repro.xnor.packing import pad_features

    x = pad_features(x)

    def run(x, packed, scale):
        dot = xops.xnor_matmul(x, packed, k=x.shape[-1], out_dtype=jnp.int32)
        return jax.lax.psum(dot, "model")

    out = (_per_device(run, w, x) - (x.shape[-1] - w.k)).astype(jnp.float32)
    if w.scale is not None:
        out = out * w.scale.astype(jnp.float32)
    return out.astype(x.dtype)


def _apply_packed_conv(w: PackedConv, x, *, stride=(1, 1), padding="SAME"):
    from repro.core.packing import unpack_bits

    kh, kw = w.ksize
    n_dim = w.packed.shape[-1]
    wb = unpack_bits(w.packed, dtype=jnp.float32)[: w.k]  # drop ragged pad
    if w.scale is not None:
        wb = wb * w.scale.astype(jnp.float32)[None, :]
    wk = wb.reshape(kh, kw, w.c_in, n_dim)
    return _apply_dense(wk, x, stride=stride, padding=padding)


def _apply_xnor_conv(w: XnorConv, x, *, stride=(1, 1), padding="SAME"):
    from repro.xnor.conv import ops as cops

    out = cops.xnor_conv2d(x, w.packed, w.scale, ksize=w.ksize, c_in=w.c_in,
                           stride=stride, padding=padding,
                           out_dtype=jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# registration
# ---------------------------------------------------------------------------

DENSE = register_backend(BackendSpec(
    name="dense", kinds=("linear", "conv"), priority=0, leaf_type=None,
    eligible=_dense_eligible, pack=_pack_dense, apply=_apply_dense,
    cost=functools.partial(costs.gemm_cost, "dense"),
    doc="Full-width master weights on the MXU (matmul or lax.conv)."))

BINARIZED_DENSE = register_backend(BackendSpec(
    name="binarized_dense", kinds=("conv",), priority=10, leaf_type=None,
    eligible=_conv_selected, pack=_pack_binarized_dense, apply=_apply_dense,
    cost=functools.partial(costs.gemm_cost, "binarized_dense"),
    tp_dim=-1,
    doc="Conv fallback: Alg.-1 binarized values (±1 [* alpha]) stored "
        "densely; runs on the ordinary conv path."))

PACKED_CONV = register_backend(BackendSpec(
    name="packed_conv", kinds=("conv",), priority=15, leaf_type=PackedConv,
    eligible=_packed_conv_eligible, pack=_pack_packed_conv,
    apply=_apply_packed_conv,
    cost=functools.partial(costs.gemm_cost, "packed"),
    tp_dim=-1,
    doc="Stoch-mode conv: binary kernel bitpacked along flattened kh*kw*C "
        "(1-bit storage), unpacked to ±1 [* alpha] at apply time onto the "
        "ordinary conv path — makes K-replica ensembles (repro.stoch) "
        "affordable for conv nets."))

PACKED = register_backend(BackendSpec(
    name="packed", kinds=("linear",), priority=20, leaf_type=PackedLinear,
    eligible=_packable,
    pack=functools.partial(_pack_linear, PackedLinear), apply=_apply_packed,
    cost=functools.partial(costs.gemm_cost, "packed"),
    tp_dim=-1,
    doc="Bitpacked binary weights, full-width activations: the MXU "
        "binary-matmul engine (repro.kernels)."))

XNOR = register_backend(BackendSpec(
    name="xnor", kinds=("linear",), priority=30, leaf_type=XnorLinear,
    eligible=_xnor_eligible,
    pack=functools.partial(_pack_linear, XnorLinear), apply=_apply_xnor,
    cost=functools.partial(costs.gemm_cost, "xnor"),
    # Row-parallel contraction sharding is exact for xnor: the partial
    # popcount sums all-reduce in int32, so sharded streams stay
    # bit-identical to single-device. The f32-accumulating packed backend
    # deliberately does NOT set tp_contract_dim.
    tp_dim=-1, tp_contract_dim=-2,
    doc="Fully-binary FC: binary weights AND sign-packed activations, "
        "XNOR-popcount dot (repro.xnor)."))

XNOR_CONV = register_backend(BackendSpec(
    name="xnor_conv", kinds=("conv",), priority=40, leaf_type=XnorConv,
    eligible=_xnor_conv_eligible, pack=_pack_xnor_conv,
    apply=_apply_xnor_conv,
    cost=functools.partial(costs.gemm_cost, "xnor_conv"),
    tp_dim=-1,
    doc="Fully-binary conv: packed im2col patches + popcount GEMM "
        "(repro.xnor.conv)."))
