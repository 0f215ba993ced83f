"""Sharding rules: logical-axis -> mesh-axis mapping (DP / TP / FSDP / EP / SP).

Models are written against *logical* activation/parameter axes and call
``ShardCtx.act(x, kind)`` at block boundaries; the context resolves the kind
to a ``PartitionSpec`` for the active mesh (or no-ops on a single device, so
smoke tests never touch device state).

Conventions (single-pod mesh ("data", "model"), multi-pod ("pod", "data",
"model")):

* batch dims           -> ("pod", "data")                  [DP]
* d_ff / expert dims   -> "model"                          [Megatron TP —
  d_ff % 16 == 0 holds for every assigned arch; asserted in tests]
* flattened heads*hd   -> "model"  (avoids head-count divisibility issues
  for the 24/40/56-head archs)
* experts              -> "model" when n_experts % 16 == 0 else unsharded
* KV-cache             -> batch over "data", sequence over "model"
  (flash-decoding-style sharded attention; XLA inserts the softmax combine)
* params               -> TP dim over "model"; with FSDP also shard the
  largest replicated dim over "data" (ZeRO-3)
* packed serving leaves (PackedLinear / XnorLinear / XnorConv)
                       -> out-channel (N) dim over "model"; the bitpacked
  int32 word dim (K // 32) is NEVER sharded, so a 32-bit lane group never
  splits across devices. ``place_packed_params`` applies these rules (or a
  compiled ExecutionPlan's recorded sharding column) to a serving tree.
"""
from __future__ import annotations

import dataclasses
import functools
import re
from typing import Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def batch_axes(mesh: Optional[Mesh]) -> tuple:
    if mesh is None:
        return ("data",)
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def make_mesh(shape, axes, devices=None) -> Mesh:
    """Builds a device mesh with ``Auto`` axes — the one constructor every
    mesh in this repo goes through.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which a sharding
    is part of an array's type and ops such as the embedding gather must
    name their output sharding. The model code places arrays and pins
    activations (``ShardCtx``) and lets GSPMD propagate the rest, which is
    what ``Auto`` axes do."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def mesh_context(mesh: Mesh):
    """Context manager activating ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


@dataclasses.dataclass
class ShardCtx:
    """Activation-sharding helper threaded through model code.

    ``decode=True`` selects the *serving* layout (``ServeEngine`` builds its
    context this way): sequence parallelism is pointless on a one-token
    stream (seq=1 cannot shard over "model" without padding permutes), so
    the residual / attention activations replicate, the KV/SSM cache drops
    its "model" axis (writes become device-local — no reshard copies), and
    the per-projection all-gathers collapse to one collective per TP matmul
    with a single deferred gather at the logits. See
    docs/ARCHITECTURE.md §Decode-step collective budget."""

    mesh: Optional[Mesh] = None
    enable: bool = True
    decode: bool = False
    #: the model code runs vmapped over a stack of replicas (the K-replica
    #: ensemble), whose placement a ``shard_map`` inside cannot name
    replicas: bool = False

    def _p(self, *spec) -> Optional[P]:
        return P(*spec)

    def slot_split(self, n_slots: int):
        """The data axes (one name or a tuple) over which the serving
        layout splits ``n_slots`` cache slots, so that per-slot cache
        writes can run per device under ``shard_map``; None where there is
        no such split: no mesh or one device, the training layout, a
        vmapped replica stack, or a slot count the data axes do not divide
        (the cache then replicates)."""
        if (not self.enable or not self.decode or self.replicas
                or self.mesh is None or self.mesh.size == 1):
            return None
        dp = batch_axes(self.mesh)
        n = 1
        for a in dp:
            n *= self.mesh.shape[a]
        if n == 1 or n_slots % n:
            return None
        return dp if len(dp) > 1 else dp[0]

    def act(self, x: jax.Array, kind: str) -> jax.Array:
        """Applies a with_sharding_constraint for a logical activation kind."""
        if not self.enable or self.mesh is None:
            return x
        dp = batch_axes(self.mesh)
        specs = {
            # Residual stream: seq over "model" = Megatron sequence
            # parallelism — GSPMD inserts the SP all-gather before each
            # TP block and the reduce-scatter after it, and the per-layer
            # scan carry (the remat-saved activation) shrinks by the TP
            # degree. See EXPERIMENTS.md §Perf iteration 1.
            "btd": P(dp, "model", None),       # (batch, seq, d_model)
            "btf": P(dp, None, "model"),       # (batch, seq, d_ff)
            "btq": P(dp, None, "model"),       # (batch, seq, heads*hd)
            "bthd": P(dp, None, "model", None),# (batch, seq, heads, hd)
            "btv": P(dp, None, "model"),       # logits (vocab TP-sharded)
            "bv": P(dp, None),                 # last-token logits, gathered
            "bte": P(dp, None, None),          # router logits (small)
            "ecd": P(None, dp, "model"),       # MoE buffer (E, cap, d)
            "ecf": P(None, dp, "model"),       # MoE hidden (E, cap, f)
            "a": P(dp),                        # MoE assignment vectors (T*k,)
            "ad": P(dp, "model"),              # MoE per-assignment acts
            "btn": P(dp, None, "model"),       # ssm inner (batch, seq, d_inner)
            "bsh": P(dp, None, "model"),       # ssm dt (batch, seq, heads)
            "bcqqh": P(dp, None, None, None, "model"),  # SSD decay blocks
            "bchpn": P(dp, None, "model", None, None),  # SSD chunk states
            "cache_kv": P(None, dp, "model", None, None),  # (L, B, S, kv, hd)
            "ssm_state": P(None, dp, "model", None, None), # (L, B, heads, hp, N)
        }
        if self.decode:
            specs.update({
                # replicated residual/attention stream: attention internals
                # (RoPE, cache write, softmax, PV einsum) run device-local
                "btd": P(dp, None, None),
                "btq": P(dp, None, None),
                "bthd": P(dp, None, None, None),
                # MLP hidden replicated too: the col-parallel up-projection
                # all-gathers its (tiny) output so the down-projection
                # contracts full-K locally — partial f32 sums behind an
                # all-reduce could change summation order vs single device
                # (the xnor row-parallel down-proj still all-reduces its
                # *integer* popcount partials, which is exact)
                "btf": P(dp, None, None),
                # one all-gather right after the col-parallel qkv matmul
                "qkv": P(dp, None, None),
                # "btv" stays V-sharded (the base spec): pinning the logits
                # dot's output replicated makes GSPMD all-gather the whole
                # tied-embedding table (weight bytes) instead of the tiny
                # (B, V) activation. The deferred gather is the separate
                # "bv" constraint applied AFTER the head matmul
                # (transformer._decode_head_out).
                # cache entries keep "model" off every axis: updates are
                # in-place local writes (donation-friendly, no reshards)
                "cache_kv": P(None, dp),
                "ssm_state": P(None, dp),
            })
        else:
            specs["qkv"] = P(dp, None, "model")   # fused qkv projection out
        spec = specs.get(kind)
        if spec is None:
            return x
        spec = P(*spec[: x.ndim])
        try:
            return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))
        except (ValueError, TypeError):
            return x


# ---------------------------------------------------------------------------
# Parameter PartitionSpecs, generated from tree paths by pattern rules.
# ---------------------------------------------------------------------------

# (path regex, spec builder given ndim). Later rules win. Cached: the
# 13-entry closure table is built once per (fsdp, dp_axes), not per leaf.
@functools.lru_cache(maxsize=None)
def _pspec_rules(fsdp: bool, dp_axes=("data",)):
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]

    def rule(last_model_dim, fsdp_dim=None):
        def build(ndim: int):
            spec = [None] * ndim
            if last_model_dim is not None:
                spec[last_model_dim % ndim] = "model"
            if fsdp and fsdp_dim is not None and (fsdp_dim % ndim) != (
                    (last_model_dim or 0) % ndim if last_model_dim is not None else -99):
                spec[fsdp_dim % ndim] = dp
            return P(*spec)
        return build

    return [
        # (V, D): vocab-parallel (Megatron embedding). TP on V keeps BOTH
        # tied-embedding consumers weight-stationary: the lookup is a
        # masked local take + one small f32 all-reduce (exact — each output
        # element is one shard's row + zeros), and the tied logits matmul
        # w.T is col-parallel on V, so no device ever moves the (V, D)
        # table. TP on D instead made GSPMD reshard+gather the whole table
        # every decode step (measured: ~60% of decode-step collective
        # bytes).
        (re.compile(r".*embed.*"), rule(-2, -1)),
        (re.compile(r".*lm_head.*"), rule(-1, -2)),          # (D, V): vocab TP
        (re.compile(r".*(scale|gamma|beta|bias|A_log|dt_bias|D)$"), rule(None)),
        (re.compile(r".*router.*"), rule(None, -2)),
        (re.compile(r".*w_qkv$"), rule(-1, -2)),             # (.., D, q+2kv): TP out
        (re.compile(r".*w_o$"), rule(-2, -1)),               # (.., q, D): TP in
        (re.compile(r".*w_(gate|up)$"), rule(-1, -2)),       # (.., D, F)
        (re.compile(r".*wi$"), rule(-1, -2)),
        (re.compile(r".*w_down$"), rule(-2, -1)),            # (.., F, D)
        (re.compile(r".*wo$"), rule(-2, -1)),
        (re.compile(r".*in_proj$"), rule(-1, -2)),           # ssm
        (re.compile(r".*out_proj$"), rule(-2, -1)),
        (re.compile(r".*conv$"), rule(-1)),                  # depthwise (w, d_inner)
    ]


def leaf_pspec(path: str, ndim: int, fsdp: bool = False,
               dp_axes=("data",)) -> P:
    """Megatron-style PartitionSpec for one *master-weight* leaf, resolved
    from its '/'-joined tree path (later rules win). This is the single
    source of the dense sharding rules: ``params_pspecs`` maps it over a
    tree, and the execution-plan compiler records it per plan row for every
    leaf a binary backend does not claim."""
    rules = _pspec_rules(bool(fsdp), tuple(dp_axes))
    chosen = P()
    for pat, build in rules:
        if pat.fullmatch(path):
            chosen = build(ndim) if ndim else P()
    # sanity: spec rank must not exceed leaf rank
    if len(chosen) > ndim:
        chosen = P(*list(chosen)[:ndim])
    return chosen


def params_pspecs(params, fsdp: bool = False, dp_axes=("data",)):
    """PartitionSpec tree matching ``params`` by path patterns.

    ``dp_axes``: the data-parallel mesh axes FSDP shards over — on the
    multi-pod mesh this must include "pod" (32-way ZeRO-3, not 16)."""

    def spec_for(path, leaf):
        from repro.core.binarize import _path_str

        return leaf_pspec(_path_str(path), getattr(leaf, "ndim", 0),
                          fsdp=fsdp, dp_axes=dp_axes)

    return jax.tree_util.tree_map_with_path(spec_for, params)


def shardings_from_pspecs(mesh: Mesh, pspecs):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# Serving-tree placement: put a packed parameter tree on a mesh, following
# the sharding column of a compiled ExecutionPlan (repro.engine.plan).
# ---------------------------------------------------------------------------

def spec_to_json(spec) -> list:
    """``PartitionSpec`` -> JSON-stable list (entries: None | str | [str..]).
    Inverse of :func:`spec_from_json`."""
    return [list(e) if isinstance(e, (tuple, list)) else e for e in spec]


def spec_from_json(entries) -> P:
    return P(*[tuple(e) if isinstance(e, list) else e for e in entries])


def _serving_leaf_types():
    from repro.engine import registry

    return registry.serving_leaf_types()


def backend_leaf_spec(path: str, master_ndim: int, backend_spec) -> Optional[P]:
    """Master-shape PartitionSpec for a leaf owned by a registered backend.

    A backend declaring ``tp_contract_dim`` opts its input-sharded
    (Megatron row-parallel) projections — the leaves whose *path rule*
    (:func:`leaf_pspec`) puts "model" on the contraction dim (w_o, wo,
    w_down, out_proj) — into contraction sharding: the packed int32 *word*
    dim splits over "model" (whole words only, so a 32-bit lane group still
    never crosses a device) and GSPMD finishes the matmul with one
    all-reduce of partial popcount sums instead of gathering and
    re-scattering the activation at the packed/dense boundary. Everything
    else falls back to the backend's out-channel ``tp_dim``. Returns None
    when the backend declares neither (dense path rules apply)."""
    cd = getattr(backend_spec, "tp_contract_dim", None)
    if cd is not None and master_ndim >= 2:
        mspec = leaf_pspec(path, master_ndim)
        entries = list(mspec) + [None] * (master_ndim - len(mspec))
        if entries[cd % master_ndim] == "model":
            return tp_spec(cd, master_ndim)
    if backend_spec.tp_dim is not None:
        return tp_spec(backend_spec.tp_dim, master_ndim)
    return None


def serving_leaf_pspec(path: str, leaf) -> P:
    """PartitionSpec for one *serving-tree* leaf (plan-free fallback).

    Consults the backend registry, so user-registered backends behave like
    the built-ins: a serving leaf whose backend declares a ``tp_dim``
    shards that master dim over "model" (for the bitpacked built-ins, the
    out-channel / N dim — never the word (K//32) dim, so a 32-bit lane
    group is never split across devices), and a backend declaring
    ``tp_contract_dim`` shards its row-parallel projections on the
    contraction/word dim instead (:func:`backend_leaf_spec` — same rules
    the plan compiler records). Plain arrays, and serving leaves whose
    backend declares neither, follow the Megatron path rules
    (:func:`leaf_pspec`)."""
    from repro.engine import registry

    from repro.core.policy import is_conv_kernel

    spec = registry.spec_for_serving_leaf(leaf)
    if spec is not None:
        shape = getattr(leaf, "master_shape", getattr(leaf, "shape", ()))
        s = backend_leaf_spec(path, len(shape), spec)
        if s is not None:
            return s
    elif is_conv_kernel(path) and getattr(leaf, "ndim", 0) == 4:
        # conv-stack kernels stay plain arrays under the binarized_dense
        # backend (and dense), so the registry cannot identify them by
        # type; TP-shard the out-channel dim like compile_plan records for
        # binarized_dense (a valid conv sharding for dense masters too)
        s = tp_spec(-1, 4)
        if s is not None:
            return s
    return leaf_pspec(path, getattr(leaf, "ndim", 0))


def _adapt_spec(spec: P, ndim: int) -> P:
    """Fit a master-shape spec onto an array of rank ``ndim`` by dropping
    the second-to-last entry per excess rank (serving layouts collapse the
    *contraction-side* master dims into the word dim, or omit them entirely:
    an XnorConv packs (kh, kw, C, N) into 2-D (words, N); a PackedLinear's
    per-channel scale drops the K dim, keeping (stack..., N)). The
    out-channel dim is last in every layout, so this alignment keeps an
    out-channel "model" on N and never leaks a row-parallel contraction
    "model" onto a stack/scale dim."""
    entries = list(spec)
    while len(entries) > max(ndim, 1):
        entries.pop(-2)
    if ndim == 0:
        entries = []
    return P(*entries)


def _place_serving_node(mesh: Mesh, spec: P, node, types=None):
    """device_put one plan row's serving node (packed leaf or plain array)
    under its master-shape spec, rank-adapting (and re-sanitizing — a word
    dim can be non-divisible where its master dim was divisible) to each
    stored array."""
    def put(a):
        if a is None or not hasattr(a, "ndim"):
            return a
        s = _adapt_spec(spec, a.ndim)
        s = sanitize_spec(mesh, s, a.shape)
        return jax.device_put(a, NamedSharding(mesh, s))

    if isinstance(node, types if types is not None
                  else _serving_leaf_types()):
        # generic over any registered pytree node class: place each stored
        # array, keep the node's static aux data
        kids, treedef = jax.tree_util.tree_flatten(node)
        return with_model_split(jax.tree_util.tree_unflatten(
            treedef, [put(a) for a in kids]), spec)
    return put(node)


def model_split(spec: P) -> Optional[str]:
    """Which dim of a (..., K, N) master-shape projection spec is split over
    "model": "n" (out-channel), "k" (contraction) or None."""
    def on_model(e):
        return e == "model" or (isinstance(e, (tuple, list)) and "model" in e)

    entries = list(spec)
    if entries and on_model(entries[-1]):
        return "n"
    if len(entries) >= 2 and on_model(entries[-2]):
        return "k"
    return None


def with_model_split(node, spec: P):
    """``node`` recording its placement split (:func:`model_split`), for
    serving leaves that carry one (a static ``tp`` field): their kernels
    run per device under shard_map on a mesh (``engine.backends``)."""
    if dataclasses.is_dataclass(node) and hasattr(node, "tp"):
        return dataclasses.replace(node, tp=model_split(spec))
    return node


def place_packed_params(mesh: Mesh, params, plan=None):
    """Place a (possibly packed) parameter tree on ``mesh``.

    With ``plan`` (a compiled :class:`repro.engine.ExecutionPlan`), each
    leaf follows its plan row's recorded sharding column; without one (or
    for v1-manifest rows), specs are re-derived from leaf types and paths
    (:func:`serving_leaf_pspec`) — equivalent for every typed serving leaf,
    while plain-array 4-D conv kernels uniformly TP-shard the out-channel
    dim (the ``binarized_dense`` rule; a dense-backend conv row's recorded
    column may instead be replicated — both placements are correct, the
    plan's is authoritative when given). Packed int32 weight words are always
    sharded on the out-channel dim over "model" (never splitting a 32-bit
    lane group); per-channel scales follow their N dim; dense leaves follow
    the Megatron rules. Axes named in a spec but absent from ``mesh`` are
    dropped (a "model"-annotated plan placed on a data-only mesh simply
    replicates those dims)."""
    from repro.core.binarize import _path_str

    types = _serving_leaf_types()                 # one registry walk, not
    is_leaf = lambda x: isinstance(x, types)      # noqa: E731 — per node
    nodes = jax.tree_util.tree_leaves_with_path(params, is_leaf=is_leaf)
    row_spec = {}
    if plan is not None:
        if len(plan.layers) != len(nodes):
            raise ValueError(
                f"plan/params mismatch: plan has {len(plan.layers)} rows, "
                f"tree has {len(nodes)} leaves")
        row_spec = {a.path: a.pspec for a in plan.layers}
    out = []
    for path, node in nodes:
        s = _path_str(path)
        if plan is not None and s not in row_spec:
            raise ValueError(
                f"plan/params mismatch: tree leaf {s!r} has no plan row "
                f"(the plan was compiled for a different tree)")
        # a v1-manifest row carries no sharding column (pspec None):
        # re-derive from the leaf type / path, same rules as compile
        spec = row_spec.get(s)
        if spec is None:
            spec = serving_leaf_pspec(s, node)
        spec = sanitize_spec(mesh, spec,
                             getattr(node, "master_shape",
                                     getattr(node, "shape", ())))
        out.append(_place_serving_node(mesh, spec, node, types))
    treedef = jax.tree_util.tree_structure(params, is_leaf=is_leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def tp_spec(tp_dim: int, ndim: int) -> Optional[P]:
    """"model"-on-one-dim spec for a backend's registered ``tp_dim`` (None
    when the leaf is not matmul-shaped). The single construction both the
    plan compiler (``engine.plan._row_sharding``) and the plan-free
    placement fallback (:func:`serving_leaf_pspec`) use, so the two paths
    cannot diverge."""
    if ndim < 2:
        return None
    entries = [None] * ndim
    entries[tp_dim % ndim] = "model"
    return P(*entries)


def sanitize_spec(mesh: Mesh, spec: P, shape) -> P:
    """Drop spec axes a concrete mesh cannot honour: axis names missing
    from the mesh, dims not divisible by their axis size (placement stays
    correct — those dims replicate), and entries beyond the array rank."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    for i, e in enumerate(spec):
        if i >= len(shape):     # spec longer than the array: truncate
            break
        axes = e if isinstance(e, (tuple, list)) else (e,)
        axes = [a for a in axes if a is not None and a in sizes]
        n = 1
        for a in axes:
            n *= sizes[a]
        if not axes or shape[i] % n != 0:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(tuple(axes))
    return P(*out)


def divisibility_report(cfg, n_model: int = 16) -> dict:
    """Which dims shard cleanly over the model axis (documented invariant)."""
    return {
        "d_ff": cfg.d_ff % n_model == 0 if cfg.d_ff else True,
        "q_dim": cfg.q_dim % n_model == 0 if cfg.has_attention else True,
        "kv_dim": cfg.kv_dim % n_model == 0 if cfg.has_attention else True,
        "d_inner": (cfg.d_inner % n_model == 0) if cfg.ssm_state else True,
        "experts": (cfg.n_experts % n_model == 0) if cfg.n_experts else True,
        "vocab": cfg.vocab_size % n_model == 0,
    }
