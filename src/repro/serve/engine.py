"""Serving engine: packed-weight inference with prefill + batched decode.

The paper's headline inference result (binarized nets cut inference time
~10x on FPGA vs the unregularized FPGA net, >25% vs GPU) maps on TPU to the
*packed-weight* serving path: projection weights are binarized once
(deterministically, Eq. 1 — the paper also evaluates inference of
stochastically-trained nets with their master-sign weights) and stored as
bitpacked int32 (+ optional per-channel scale), so decode — a weight-bytes-
bound workload — moves ~16x fewer HBM bytes.

Which datapath each layer gets is decided by the execution-plan compiler
(``repro.engine``): ``pack_params`` is a thin wrapper over
``compile_plan(...).pack(params)``, and the model code dispatches through
``apply_linear``/``apply_conv2d`` on the serving leaf types the plan
produced. Compile the plan yourself to inspect, save, or override the
per-layer assignment (``launch.serve --plan-report`` prints it).

Serving is *step-level continuously batched* (:func:`stream_serve`): the
KV cache is a persistent, slot-addressed structure (``DecodeState``), a
finished request's slot is re-prefilled from the queue mid-stream
(``ServeEngine.prefill_into``), and one fixed-shape jitted ``decode_step``
advances all slots each step — sustained streaming throughput rather than
round-based batch latency, which is where the binarized datapaths' byte
savings actually pay off (cf. FINN, arXiv:1612.07119).

Serving is also *mesh-shardable*: ``ServeEngine(cfg, params, mesh=mesh,
plan=plan)`` places the packed tree and the slot-addressed decode cache on
a ("data", "model") mesh following the plan's sharding column — the
paper-to-TPU analogue of FINN-style datapath widening: BNN throughput comes
from scaling the datapath wide across compute units, not from one unit.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.binarize import BinarizeMode
from repro.engine import compile_plan
from repro.models import transformer as T
from repro.models.layers import (PackedConv, PackedLinear, XnorConv,
                                 XnorLinear)
from repro.obs.trace import NULL_TRACER

# Every serving program rounds to bf16 exactly where the model code casts.
# With XLA's default excess precision a fusion may skip such a rounding
# (e.g. square the f32 residual sum inside the RMSNorm fusion instead of
# its bf16 value), and which ones it skips depends on the fusions — on a
# mesh the collectives move the fusion boundaries, so the same step would
# round differently on one device and on many, and greedy streams drift.
_jit = functools.partial(
    jax.jit, compiler_options={"xla_allow_excess_precision": False})


def pack_params(params, policy, mode: str | BinarizeMode = "det",
                key: Optional[jax.Array] = None, with_scale: bool = True,
                xnor_policy=None, overrides=None):
    """Binarize+bitpack every policy-selected >=2-D projection leaf.

    Equivalent to ``repro.engine.compile_plan(...).pack(params, key)`` —
    kept as the one-call convenience entry point. Stacked leaves (L, K, N)
    pack per layer via vmap; the resulting PackedLinear children keep the
    leading stack dims so ``lax.scan`` slices them exactly like dense
    leaves. MoE expert tensors (E-stacked) pack the same way. ``with_scale``
    stores the per-output-channel mean |w| (BWN alpha) so packed inference
    tracks the master weights' magnitude.

    ``mode="xnor"`` selects the fully-binary engine: weights binarize
    deterministically (Eq. 1) exactly as ``mode="det"``, but leaves *also*
    selected by ``xnor_policy`` (default ``core.policy.XNOR_POLICY``) land
    on the ``xnor`` / ``xnor_conv`` backends (activations sign-binarized +
    bitpacked on the fly, XNOR-popcount compute). Policy-selected conv
    kernels with no binary lowering serve Alg.-1 binarized values stored
    densely (the ``binarized_dense`` backend); policy-selected projections
    that cannot bitpack (K % 32 != 0, ndim < 2) serve dense — no longer
    silently: the compiled plan records the reason per layer and warns.
    See ``repro.engine`` for the backend registry and
    ``core.policy.XNOR_POLICY`` for the real-valued-input boundary."""
    plan = compile_plan(params, policy, mode, xnor_policy=xnor_policy,
                        with_scale=with_scale, overrides=overrides)
    return plan.pack(params, key=key)


def packed_param_bytes(params) -> tuple[int, int]:
    """(dense bf16 bytes, packed bytes) over policy-packed leaves.

    The dense baseline is derived from each serving leaf's recorded
    *master-weight* shape (``leaf.master_shape``, stack dims included) —
    never from the packed array's word counts, which over-state K whenever
    a layout carries self-cancelling pad words (the xnor conv engine's
    per-tap channel padding, or any future padded layout). The packed side
    counts the int32 words actually stored (pad words are real bytes)."""
    dense = packed = 0
    packed_types = (PackedLinear, XnorLinear, XnorConv, PackedConv)
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, packed_types)):
        if isinstance(leaf, packed_types):
            n_master = 1
            for d in leaf.master_shape:
                n_master *= d
            dense += n_master * 2
            packed += leaf.packed.size * 4
            if leaf.scale is not None:
                packed += leaf.scale.size * 4
        else:
            dense += leaf.size * 2
            packed += leaf.size * 2
    return dense, packed


# ---------------------------------------------------------------------------
# generation
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class GenerationResult:
    """Logprob convention: ``logprobs[b, i]`` is the log-probability of
    ``tokens[b, i]`` under the distribution the token was actually drawn
    from — ``softmax(logits / temperature)`` when sampling, ``softmax(
    logits)`` for greedy decoding (temperature 0). Tempered logprobs are
    therefore comparable across tokens of one generation but not across
    runs at different temperatures.

    The ensemble fields are populated only when the engine serves a
    K >= 2 :class:`repro.stoch.ReplicaSet` (None otherwise):
    ``vote_agreement[b, i]`` is the fraction of replicas whose argmax at
    step i matched the ensemble vote, ``logit_variance[b, i]`` the mean
    across-replica logit variance, and ``abstained[b]`` flags generations
    whose worst-step agreement fell below the engine's
    ``abstain_threshold``."""

    tokens: jax.Array          # (B, max_new)
    logprobs: jax.Array        # (B, max_new)
    steps: int
    logit_variance: Optional[jax.Array] = None   # (B, max_new) f32
    vote_agreement: Optional[jax.Array] = None   # (B, max_new) f32
    abstained: Optional[jax.Array] = None        # (B,) bool


@dataclasses.dataclass
class DecodeState:
    """Live state of the step-level continuous-batching engine: one
    long-lived, slot-addressed KV cache plus the next-token logits of every
    slot. Requests come and go (``prefill_into``); the state's shapes never
    change, so the jitted decode step never re-specializes."""

    cache: dict                # slot-addressed decode cache (B = n_slots);
                               # ensemble serving adds a leading (K,) axis
    logits: jax.Array          # (n_slots, vocab) next-token logits per slot
    n_slots: int
    prompt_len: int
    max_new_cap: int           # per-request max_new must be <= this
    # Ensemble-serving uncertainty of each slot's current logits (None on
    # the single-sample path): replica vote agreement and mean logit
    # variance, refreshed by every prefill_into / decode_step.
    agreement: Optional[jax.Array] = None        # (n_slots,) f32
    variance: Optional[jax.Array] = None         # (n_slots,) f32

    @property
    def context_len(self) -> int:
        return self.prompt_len + self.max_new_cap


class ServeEngine:
    """Batched prefill + greedy/temperature decode over a (possibly packed)
    parameter tree.

    Two serving modes share the same jitted model functions:

    * one-shot: ``generate(prompts, max_new)`` — prefill a batch, decode
      every row for ``max_new`` steps (the tier-1 parity oracle);
    * step-level continuous batching: ``init_decode`` builds a persistent
      slot-addressed :class:`DecodeState`, ``prefill_into`` splices a fresh
      request into a live cache at a slot index, and ``decode_step``
      advances *all* slots one token with a single fixed-shape jitted call.
      ``stream_serve`` drives the loop against a ``SlotBatcher``.

    **Mesh-sharded serving.** Pass ``mesh`` (a ``jax.sharding.Mesh`` with
    "data"/"model" axes) to serve tensor-parallel: the engine places the
    parameter tree on the mesh (packed int32 weight words TP-sharded over
    "model" on the out-channel dim — a 32-bit lane group never splits
    across devices; dense leaves on the Megatron rules), builds a
    ``ShardCtx`` so activation constraints thread through the
    ``apply_linear``/``apply_conv2d`` dispatch, and places the persistent
    decode cache with slots over "data" (``models.transformer.
    cache_pspecs``). All jitted entry points run under ``mesh_context``.
    Pass the ``plan`` the tree was packed with to follow its recorded
    sharding column exactly (otherwise equivalent rules are re-derived
    from leaf types and paths). Greedy streams stay bit-identical to the
    single-device engine (asserted in ``tests/test_distributed.py``).
    """

    def __init__(self, cfg, params, sh=None, *, mesh=None, plan=None,
                 ensemble=None, abstain_threshold: Optional[float] = None,
                 tracer=None):
        self.cfg = cfg
        self.mesh = mesh
        self.abstain_threshold = abstain_threshold
        # Observability (repro.obs): a span around every jitted entry point
        # (the host's enqueue; device time comes from a profiler trace,
        # whose host plane carries these spans). The default NULL_TRACER
        # makes every span site a no-op.
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._replicas = None
        if ensemble is not None:
            from repro.stoch import ReplicaSet

            if not isinstance(ensemble, ReplicaSet):
                raise TypeError(
                    f"ensemble= expects a repro.stoch.ReplicaSet "
                    f"(sample_replicas(...)), got {type(ensemble).__name__}")
            if params is not None and params is not ensemble.base:
                raise ValueError(
                    "pass either params or ensemble=ReplicaSet, not both "
                    "(the ensemble's base tree is the parameter tree)")
            plan = plan if plan is not None else ensemble.plan
        if mesh is not None:
            from repro.distributed.sharding import (ShardCtx,
                                                    place_packed_params)

            if sh is None:
                # decode=True: the serving activation layout — no sequence
                # parallelism on the one-token stream, replicated residual,
                # model-replicated cache (local in-place writes), one
                # deferred logits gather. See ShardCtx and
                # docs/ARCHITECTURE.md §Decode-step collective budget.
                sh = ShardCtx(mesh, decode=True)
            if ensemble is not None:
                from repro.stoch import place_replicas

                ensemble = place_replicas(mesh, ensemble, plan)
                params = ensemble.base
            else:
                params = place_packed_params(mesh, params, plan)
        elif ensemble is not None:
            params = ensemble.base
        elif plan is not None:
            raise ValueError("ServeEngine(plan=...) only places params on a "
                             "mesh; pass mesh= as well (or drop plan=)")
        self.params = params
        self.sh = sh
        self._prefill = _jit(
            lambda p, toks, ml: T.prefill(cfg, p, toks, sh, max_len=ml),
            static_argnums=2)
        # The persistent cache is donated: the per-step KV write updates the
        # long-lived buffer in place instead of copying the whole cache per
        # token. Every caller (generate / decode_step / decode_steps)
        # rebinds its state to the returned cache, so the consumed input
        # buffer is never touched again. _pin_state pins the returned state
        # to the init_decode placement: left unconstrained, GSPMD may pick a
        # different output layout (e.g. xnor's row-parallel w_o propagates
        # KV-heads-over-"model" onto the returned cache), which breaks the
        # input==output sharding invariant donation relies on and retraces
        # the jit into a slower steady-state program than the audited one.
        def _decode_fn(p, cache, tok):
            lg, cache = T.decode_step(cfg, p, cache, tok, sh)
            cache, lg = self._pin_state(cache, lg)
            return lg, cache

        self._decode = _jit(_decode_fn, donate_argnums=(1,))

        def _decode_chunk(p, cache, logits, d):
            """d fixed-shape greedy decode steps under one lax.scan: emits
            the argmax token per slot per step and leaves ``logits`` at the
            next-token logits (the DecodeState invariant), so the serving
            loop crosses the host boundary once per d tokens."""
            def body(carry, _):
                cache, logits = carry
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                lg, cache = T.decode_step(cfg, p, cache, tok[:, None], sh)
                return (cache, lg.astype(logits.dtype)), tok

            (cache, logits), toks = jax.lax.scan(
                body, (cache, logits), None, length=d)
            cache, logits = self._pin_state(cache, logits)
            return cache, logits, jnp.moveaxis(toks, 0, 1)  # (n_slots, d)

        self._decode_chunk = _jit(_decode_chunk, static_argnums=3,
                                  donate_argnums=(1, 2))

        def _prefill_into(p, cache, logits, prompt, slot, ml):
            lg, one = T.prefill(cfg, p, prompt, sh, max_len=ml)
            logits = jax.lax.dynamic_update_slice_in_dim(
                logits, lg.astype(logits.dtype), slot, axis=0)
            cache = T.cache_insert(cfg, cache, one, slot)
            cache, logits = self._pin_state(cache, logits)
            return logits, cache

        self._prefill_into = _jit(_prefill_into, static_argnums=5)

        def _prefill_chunk(p, cache, logits, chunk_toks, slot, offset):
            """One prefill chunk for one slot, no decode (the ramp-up /
            drain path when no other slot is actively decoding). Donated
            like the decode programs: the chunk's rows land in the live
            cache instead of a whole-cache copy."""
            lg, cache = T.prefill_chunk(cfg, p, cache, chunk_toks, slot,
                                        offset, sh)
            logits = jax.lax.dynamic_update_slice_in_dim(
                logits, lg.astype(logits.dtype), slot, axis=0)
            cache, logits = self._pin_state(cache, logits)
            return logits, cache

        self._prefill_chunk = _jit(_prefill_chunk, donate_argnums=(1, 2))

        def _decode_prefill(p, cache, logits, tok, keep, chunk_toks, slot,
                            offset):
            """The fused steady-state step of chunked prefill: advance all
            live decode slots one token AND one slot's prefill by one chunk,
            in a single fixed-shape program. ``keep`` (n_slots,) bool marks
            mid-prefill slots whose logits and non-rewritable cache state
            must survive the batched decode: cache_keep re-selects the old
            position counters and recurrent ssm/conv states bit-exactly
            (append-style K/V writes land where the slot's next chunk
            overwrites them — see its docstring) before the chunk runs."""
            dec_lg, dec_cache = T.decode_step(cfg, p, cache, tok, sh)
            cache = T.cache_keep(cfg, cache, dec_cache, keep)
            logits = jnp.where(keep[:, None], logits,
                               dec_lg.astype(logits.dtype))
            lg, cache = T.prefill_chunk(cfg, p, cache, chunk_toks, slot,
                                        offset, sh)
            logits = jax.lax.dynamic_update_slice_in_dim(
                logits, lg.astype(logits.dtype), slot, axis=0)
            cache, logits = self._pin_state(cache, logits)
            return logits, cache

        self._decode_prefill = _jit(_decode_prefill, donate_argnums=(1, 2))

        def _splice(cache, logits, one, lg, slot, use_lg):
            """Splice a prefix-cache snapshot (batch-1 rows) into a slot;
            ``use_lg`` (static) also installs the snapshot's first-token
            logits (full-prompt hits)."""
            cache = T.cache_insert(cfg, cache, one, slot)
            if use_lg:
                logits = jax.lax.dynamic_update_slice_in_dim(
                    logits, lg.astype(logits.dtype), slot, axis=0)
            cache, logits = self._pin_state(cache, logits)
            return logits, cache

        self._splice = _jit(_splice, static_argnums=5)

        def _extract(cache, logits, slot):
            """Batch-1 snapshot of one slot's cache rows + logits row (the
            capture side of the prefix cache)."""
            one = T.cache_extract(cfg, cache, slot)
            lg = jax.lax.dynamic_slice_in_dim(logits, slot, 1, axis=0)
            return one, lg

        self._extract = _jit(_extract)

        # K = 1 (or no stochastic rows) degrades to the plain single-sample
        # path above on ensemble.base — structurally the same program, so
        # the ensemble flag costs nothing and k=1 stays bit-identical.
        if ensemble is not None and ensemble.k > 1 and ensemble.stacked:
            self._replicas = ensemble
            self._build_ensemble_fns()

    def _pin_state(self, cache, logits):
        """Constrain a decode state (cache dict + next-token logits) to the
        ``init_decode`` placement, inside a jit trace. Keeps every decode /
        prefill_into output on the exact sharding the persistent buffers
        were allocated with, so the steady-state program is the same one
        the collective audit measured and donation never hits an
        input/output sharding mismatch."""
        if self.mesh is None:
            return cache, logits
        from jax.sharding import NamedSharding
        from repro.distributed.sharding import batch_axes, sanitize_spec

        pspecs = T.cache_pspecs(self.cfg, batch_axes(self.mesh))

        def pin(a, spec):
            spec = sanitize_spec(self.mesh, spec, a.shape)
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(self.mesh, spec))

        cache = {k: pin(v, pspecs[k]) for k, v in cache.items()}
        return cache, pin(logits, pspecs["pos"])

    def _pin_ens_cache(self, cache):
        """Replica-axis variant of ``_pin_state`` for the K-stacked
        ensemble cache (same placement ``init_decode`` uses)."""
        if self.mesh is None:
            return cache
        from jax.sharding import NamedSharding
        from repro.distributed.sharding import batch_axes, sanitize_spec
        from repro.stoch.ensemble import prepend_replica_axis

        ax = self._replicas.plan.replica_axis
        pspecs = T.cache_pspecs(self.cfg, batch_axes(self.mesh))
        out = {}
        for k, v in cache.items():
            spec = sanitize_spec(self.mesh,
                                 prepend_replica_axis(ax, pspecs[k]), v.shape)
            out[k] = jax.lax.with_sharding_constraint(
                v, NamedSharding(self.mesh, spec))
        return out

    def _build_ensemble_fns(self):
        """Jitted K-replica variants of prefill / decode / prefill_into:
        one vmap over the stacked stochastic leaves (and, for decode, the
        replicated cache axis), shared base leaves broadcast by closure,
        replica logits condensed to EnsembleStats inside the jit."""
        from repro.stoch import ensemble_stats
        from repro.stoch.replicas import _substitute

        cfg, sh, k = self.cfg, self.sh, self._replicas.k

        def _ens_prefill(stacked, base, toks, ml):
            def one(st):
                return T.prefill(cfg, _substitute(base, st), toks, sh,
                                 max_len=ml)

            rep_lg, rep_cache = jax.vmap(one, in_axes=0, axis_size=k)(stacked)
            return ensemble_stats(rep_lg), rep_cache

        self._prefill_ens = _jit(_ens_prefill, static_argnums=3)

        sh_rep = (dataclasses.replace(sh, replicas=True) if sh is not None
                  else None)

        def _ens_decode(stacked, base, cache, tok):
            def one(st, c):
                return T.decode_step(cfg, _substitute(base, st), c, tok,
                                     sh_rep)

            rep_lg, cache = jax.vmap(one, in_axes=(0, 0),
                                     axis_size=k)(stacked, cache)
            return ensemble_stats(rep_lg), self._pin_ens_cache(cache)

        # same donation contract as the single-sample _decode: the
        # K-replica cache updates in place, callers rebind their state
        self._decode_ens = _jit(_ens_decode, donate_argnums=(2,))

        def _ens_prefill_into(stacked, base, cache, logits, agree, var,
                              prompt, slot, ml):
            def one(st, c):
                lg, onec = T.prefill(cfg, _substitute(base, st), prompt, sh,
                                     max_len=ml)
                return lg, T.cache_insert(cfg, c, onec, slot)

            rep_lg, cache = jax.vmap(one, in_axes=(0, 0),
                                     axis_size=k)(stacked, cache)
            es = ensemble_stats(rep_lg)          # mean (1, V); stats (1,)
            upd = jax.lax.dynamic_update_slice_in_dim
            return (upd(logits, es.mean_logits.astype(logits.dtype), slot, 0),
                    upd(agree, es.agreement, slot, 0),
                    upd(var, es.variance, slot, 0),
                    self._pin_ens_cache(cache))

        self._ens_prefill_into = _jit(_ens_prefill_into, static_argnums=8)

    def jit_entries(self) -> dict:
        """Name -> jitted entry point, for observability wrappers (the
        retrace sentinel watches these caches during ``stream_serve``).
        Ensemble entries appear only when the engine serves replicas;
        ``decode_chunk`` legitimately compiles one program per distinct
        chunk length (allowlisted by the sentinel's default)."""
        entries = {"prefill": self._prefill, "decode": self._decode,
                   "decode_chunk": self._decode_chunk,
                   "prefill_into": self._prefill_into,
                   "prefill_chunk": self._prefill_chunk,
                   "decode_prefill": self._decode_prefill,
                   "splice": self._splice, "extract": self._extract}
        for name in ("_prefill_ens", "_decode_ens", "_ens_prefill_into"):
            fn = getattr(self, name, None)
            if fn is not None:
                entries[name.strip("_")] = fn
        return entries

    def _mesh_ctx(self):
        """Ambient-mesh context for every jitted call (no-op off-mesh)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.distributed.sharding import mesh_context

        return mesh_context(self.mesh)

    def generate(self, prompts: jax.Array, max_new: int,
                 temperature: float = 0.0,
                 key: Optional[jax.Array] = None) -> GenerationResult:
        if temperature > 0.0 and key is None:
            raise ValueError(
                "temperature-sampled generation requires a PRNG key: pass "
                "key=jax.random.key(...) to generate(), or use "
                "temperature=0.0 for greedy decoding")
        if self._replicas is not None:
            return self._generate_ensemble(prompts, max_new, temperature, key)
        b, s = prompts.shape[0], prompts.shape[1]
        with self._mesh_ctx():
            logits, cache = self._prefill(self.params, prompts, s + max_new)
            toks, lps = [], []
            tok = None
            for i in range(max_new):
                if temperature > 0.0:
                    key, sub = jax.random.split(key)
                    sample_logits = logits.astype(jnp.float32) / temperature
                    tok = jax.random.categorical(sub, sample_logits, axis=-1)
                else:
                    sample_logits = logits.astype(jnp.float32)
                    tok = jnp.argmax(logits, axis=-1)
                # logprob under the *sampled* (tempered) distribution — see
                # GenerationResult for the convention
                lp = jax.nn.log_softmax(sample_logits, axis=-1)
                lps.append(jnp.take_along_axis(lp, tok[:, None],
                                               axis=-1)[:, 0])
                toks.append(tok)
                if i < max_new - 1:
                    logits, cache = self._decode(self.params, cache,
                                                 tok[:, None])
        return GenerationResult(jnp.stack(toks, 1), jnp.stack(lps, 1), max_new)

    def _generate_ensemble(self, prompts, max_new, temperature, key):
        """One-shot generation over all K replicas: tokens decode from the
        ensemble-mean logits; every step also records vote agreement and
        logit variance (same sampling/logprob conventions as the plain
        path, applied to the mean logits)."""
        rs = self._replicas
        s = prompts.shape[1]
        with self._mesh_ctx():
            es, cache = self._prefill_ens(rs.stacked, rs.base, prompts,
                                          s + max_new)
            toks, lps, agrs, vrs = [], [], [], []
            for i in range(max_new):
                logits = es.mean_logits                  # already f32
                if temperature > 0.0:
                    key, sub = jax.random.split(key)
                    sample_logits = logits / temperature
                    tok = jax.random.categorical(sub, sample_logits, axis=-1)
                else:
                    sample_logits = logits
                    tok = jnp.argmax(logits, axis=-1)
                lp = jax.nn.log_softmax(sample_logits, axis=-1)
                lps.append(jnp.take_along_axis(lp, tok[:, None],
                                               axis=-1)[:, 0])
                toks.append(tok)
                agrs.append(es.agreement)
                vrs.append(es.variance)
                if i < max_new - 1:
                    es, cache = self._decode_ens(rs.stacked, rs.base, cache,
                                                 tok[:, None])
        agreement = jnp.stack(agrs, 1)
        abstained = None
        if self.abstain_threshold is not None:
            abstained = jnp.min(agreement, axis=1) < self.abstain_threshold
        return GenerationResult(
            jnp.stack(toks, 1), jnp.stack(lps, 1), max_new,
            logit_variance=jnp.stack(vrs, 1), vote_agreement=agreement,
            abstained=abstained)

    # -- step-level continuous batching -----------------------------------

    def init_decode(self, n_slots: int, prompt_len: int,
                    max_new_cap: int) -> DecodeState:
        """Allocate the persistent decode state: a zeroed slot-addressed
        cache sized for ``prompt_len + max_new_cap`` context positions and
        an empty next-token logits buffer. Slots fill via ``prefill_into``;
        empty slots decode padding and are masked out by the caller.

        On a mesh, the state is *placed*, not just allocated: slots shard
        over the data axes and KV sequence / SSM heads over "model"
        (``models.transformer.cache_pspecs``), so the long-lived cache
        bytes — the decode working set — scale down per device."""
        ctx = prompt_len + max_new_cap
        cache = T.init_cache(self.cfg, n_slots, ctx)
        ens = self._replicas
        agreement = variance = None
        if ens is not None:
            # one cache per replica: a leading (K,) axis on every entry,
            # kept resident across decode steps; the uncertainty columns
            # start at the no-signal values (full agreement, zero variance)
            cache = {k: jnp.zeros((ens.k,) + v.shape, v.dtype)
                     for k, v in cache.items()}
            agreement = jnp.ones((n_slots,), jnp.float32)
            variance = jnp.zeros((n_slots,), jnp.float32)
        logits = jnp.zeros((n_slots, self.cfg.vocab_size),
                           jnp.float32 if ens is not None
                           else self.cfg.activation_dtype)
        if self.mesh is not None:
            from jax.sharding import NamedSharding
            from repro.distributed.sharding import batch_axes, sanitize_spec

            pspecs = T.cache_pspecs(self.cfg, batch_axes(self.mesh))
            if ens is not None:
                from repro.stoch.ensemble import prepend_replica_axis

                pspecs = {k: prepend_replica_axis(ens.plan.replica_axis, s)
                          for k, s in pspecs.items()}

            def put(a, spec):
                spec = sanitize_spec(self.mesh, spec, a.shape)
                return jax.device_put(a, NamedSharding(self.mesh, spec))

            cache = {k: put(v, pspecs[k]) for k, v in cache.items()}
            # logits (n_slots, vocab): slot dim placed exactly like the
            # cache's pos/slot axes (same one-axis spec), vocab replicated
            slot_spec = T.cache_pspecs(self.cfg,
                                       batch_axes(self.mesh))["pos"]
            logits = put(logits, slot_spec)
            if ens is not None:
                agreement = put(agreement, slot_spec)
                variance = put(variance, slot_spec)
        return DecodeState(cache, logits, n_slots, prompt_len, max_new_cap,
                           agreement=agreement, variance=variance)

    def prefill_into(self, state: DecodeState, slot: int,
                     prompt) -> DecodeState:
        """Prefill one request (prompt of static length ``prompt_len``) and
        splice its cache + first-token logits into the live state at slot
        index ``slot``. One compiled program serves every slot (the index
        is a traced scalar; all shapes are static)."""
        tr = self.tracer
        prompt = jnp.asarray(prompt, jnp.int32).reshape(1, state.prompt_len)
        if self._replicas is not None:
            rs = self._replicas
            with tr.span("prefill_into", slot=slot), self._mesh_ctx():
                logits, agree, var, cache = self._ens_prefill_into(
                    rs.stacked, rs.base, state.cache, state.logits,
                    state.agreement, state.variance, prompt,
                    jnp.int32(slot), state.context_len)
            return dataclasses.replace(state, cache=cache, logits=logits,
                                       agreement=agree, variance=var)
        with tr.span("prefill_into", slot=slot), self._mesh_ctx():
            logits, cache = self._prefill_into(
                self.params, state.cache, state.logits, prompt,
                jnp.int32(slot), state.context_len)
        return dataclasses.replace(state, cache=cache, logits=logits)

    def decode_step(self, state: DecodeState, tokens) -> DecodeState:
        """Advance every slot one token (single fixed-shape jitted call).
        ``tokens``: (n_slots,) int32 — the token just emitted per slot;
        inactive slots feed padding and their outputs are ignored."""
        tr = self.tracer
        tokens = jnp.asarray(tokens, jnp.int32).reshape(state.n_slots, 1)
        if self._replicas is not None:
            rs = self._replicas
            with tr.span("decode_step"), self._mesh_ctx():
                es, cache = self._decode_ens(rs.stacked, rs.base,
                                             state.cache, tokens)
            return dataclasses.replace(
                state, cache=cache,
                logits=es.mean_logits.astype(state.logits.dtype),
                agreement=es.agreement, variance=es.variance)
        with tr.span("decode_step"), self._mesh_ctx():
            logits, cache = self._decode(self.params, state.cache, tokens)
        return dataclasses.replace(state, cache=cache, logits=logits)

    def decode_steps(self, state: DecodeState, d: int):
        """Advance every slot ``d`` greedy tokens in ONE jitted call (a
        fixed-shape ``lax.scan`` over ``d`` decode steps — argmax, decode,
        repeat — with the cache and logits donated through the scan).
        Returns ``(new_state, tokens)`` with ``tokens`` a (n_slots, d)
        int32 *device* array: the caller decides when to cross the host
        boundary (``jax.device_get``), so the steady-state serving loop
        pays one transfer per ``d`` tokens instead of one per token.

        Greedy only: temperature sampling threads a PRNG key per step and
        stays on the one-step path. ``state.logits`` keeps the DecodeState
        invariant (the not-yet-emitted next-token logits). Compiles one
        program per distinct ``d``; ``stream_serve`` uses a fixed chunk
        size clipped to the shortest live request, so at most
        ``decode_chunk`` variants exist."""
        if self._replicas is not None:
            raise NotImplementedError(
                "decode_steps is single-sample only; ensemble serving "
                "decodes one step at a time (stream_serve falls back)")
        tr = self.tracer
        with tr.span("decode_steps", d=d), self._mesh_ctx():
            cache, logits, toks = self._decode_chunk(
                self.params, state.cache, state.logits, int(d))
        return dataclasses.replace(state, cache=cache, logits=logits), toks

    # -- chunked prefill + prefix reuse ------------------------------------

    def _require_single_sample(self, what: str) -> None:
        if self._replicas is not None:
            raise NotImplementedError(
                f"{what} is single-sample only; K-replica ensemble serving "
                f"prefills whole prompts (stream_serve falls back)")

    def prefill_chunk_into(self, state: DecodeState, slot: int, tokens,
                           offset: int) -> DecodeState:
        """Advance one slot's prefill by a chunk of prompt tokens (no
        decode): the ramp-up / drain path of chunked prefill. ``offset``
        is the number of prompt tokens already in the slot."""
        self._require_single_sample("prefill_chunk_into")
        tr = self.tracer
        toks = jnp.asarray(tokens, jnp.int32).reshape(1, -1)
        with tr.span("prefill_chunk", slot=slot, offset=int(offset),
                     c=int(toks.shape[1])), self._mesh_ctx():
            logits, cache = self._prefill_chunk(
                self.params, state.cache, state.logits, toks,
                jnp.int32(slot), jnp.int32(offset))
        return dataclasses.replace(state, cache=cache, logits=logits)

    def fused_step(self, state: DecodeState, tokens, keep_mask, slot: int,
                   chunk_tokens, offset: int) -> DecodeState:
        """The chunked-prefill steady state: ONE fixed-shape jitted call
        advances every live decode slot one token AND one slot's prefill by
        one chunk, so an arriving prompt never stalls the stream.
        ``tokens``: (n_slots,) just-emitted tokens; ``keep_mask``:
        (n_slots,) bool, True for mid-prefill slots whose state must
        survive the batched decode."""
        self._require_single_sample("fused_step")
        tr = self.tracer
        tokens = jnp.asarray(tokens, jnp.int32).reshape(state.n_slots, 1)
        keep = jnp.asarray(np.asarray(keep_mask, bool))
        toks = jnp.asarray(chunk_tokens, jnp.int32).reshape(1, -1)
        with tr.span("decode_prefill", slot=slot, offset=int(offset),
                     c=int(toks.shape[1])), self._mesh_ctx():
            logits, cache = self._decode_prefill(
                self.params, state.cache, state.logits, tokens, keep,
                toks, jnp.int32(slot), jnp.int32(offset))
        return dataclasses.replace(state, cache=cache, logits=logits)

    def capture_slot(self, state: DecodeState, slot: int):
        """Host (numpy) snapshot of one slot's cache rows + logits row —
        the capture side of the prefix cache. One explicit device->host
        transfer, at a chunk boundary (never in the decode steady state)."""
        self._require_single_sample("capture_slot")
        tr = self.tracer
        with tr.span("prefix_capture", slot=slot), self._mesh_ctx():
            one, lg = self._extract(state.cache, state.logits,
                                    jnp.int32(slot))
        return jax.device_get(one), jax.device_get(lg)

    def splice_into(self, state: DecodeState, slot: int, cache_rows: dict,
                    logits_row=None) -> DecodeState:
        """Splice a prefix-cache snapshot into a slot (prefix-cache hit).
        With ``logits_row`` (full-prompt snapshot) the slot is immediately
        decodable; otherwise chunked prefill continues from the snapshot's
        offset."""
        self._require_single_sample("splice_into")
        tr = self.tracer
        use_lg = logits_row is not None
        lg = (jnp.asarray(logits_row) if use_lg
              else jnp.zeros((1, state.logits.shape[1]),
                             state.logits.dtype))
        one = {k: jnp.asarray(v) for k, v in cache_rows.items()}
        with tr.span("prefix_splice", slot=slot,
                     full=bool(use_lg)), self._mesh_ctx():
            logits, cache = self._splice(state.cache, state.logits, one,
                                         lg, jnp.int32(slot), use_lg)
        return dataclasses.replace(state, cache=cache, logits=logits)


def stream_serve(engine: ServeEngine, batcher, *,
                 max_new_cap: Optional[int] = None,
                 temperature: float = 0.0,
                 key: Optional[jax.Array] = None,
                 metrics=None,
                 decode_chunk: int = 1,
                 sentinel=None,
                 prefill_chunk: int = 0,
                 prefix_cache=None,
                 arrivals=None) -> int:
    """Step-level continuous-batching serving loop.

    Each iteration: retire finished requests and re-prefill their slots
    from the queue (``batcher.refill``), emit one token for every active
    slot from the state's next-token logits, then run one masked decode
    step over all slots. A request finishing mid-stream frees its slot for
    the next queued request on the *next step* — no round barrier, and
    per-request ``max_new`` is honored exactly (``batcher.record`` stops
    appending at each request's own limit).

    ``max_new_cap`` sizes the persistent cache (default: the max over the
    currently queued requests); submitting a request with a larger
    ``max_new`` later raises. Returns the number of batched token-emission
    steps (the final emission needs no trailing decode_step, so the model
    runs ``steps - 1`` decode steps plus one prefill per request).

    ``decode_chunk > 1`` (greedy, non-ensemble serving only) switches the
    steady state onto the multi-step inner loop: each iteration runs
    ``d = min(decode_chunk, shortest live request's remaining budget)``
    decode steps in ONE jitted call (``ServeEngine.decode_steps``) and
    crosses the host boundary once per ``d`` tokens (a single explicit
    ``jax.device_get``). Clipping ``d`` to ``batcher.min_remaining()``
    keeps slot turnover on the chunk boundary, so refill timing — and
    therefore every emitted stream — is bit-identical to ``decode_chunk=1``
    (asserted in tests/test_distributed.py). Temperature sampling and
    K-replica ensemble serving fall back to the one-step loop.

    Observability: the engine's tracer (``ServeEngine(tracer=...)``) wraps
    the whole loop in a ``stream_serve`` span with an ``arrivals`` span
    (around the hook, when one is given) and one ``step`` span per
    iteration (``refill`` / ``sample`` / ``record`` children; the engine
    adds ``prefill_into`` / ``decode_step`` / ...; every device->host
    crossing of sampled tokens is a ``token_sync`` span). Each admitted
    request's ledger entry gets the loop iteration (counted from 1) that
    admitted it (``admit_step``), the one at which its whole prompt was in
    (``ready_step``) and the prompt chunks run for it (``prefill_chunks``).
    Pass ``metrics`` (a ``repro.obs.MetricsRegistry``) to record per-step
    latency, queue depth and slot occupancy histograms, prefill/step/token
    counters, the request-ledger TTFT/latency histograms, and a
    ``serve_tok_per_s`` gauge — the numbers ``serve_bench`` and
    ``launch.serve --metrics-out`` report.

    ``sentinel`` (a ``repro.analysis.RetraceSentinel``) is stepped once
    per loop iteration after its decode, recording any post-warmup jit
    recompile of the engine's entry points — the silent
    retrace-every-step failure mode (``launch.serve --analyze`` wires
    this up; strict sentinels raise at the offending step).

    ``prefill_chunk > 0`` (single-sample serving only) switches prompt
    admission onto *chunked prefill*: instead of one whole-prompt
    ``prefill_into`` that stalls every live decode slot, an arriving
    prompt is consumed ``prefill_chunk`` tokens at a time by the fused
    ``decode_prefill`` step — each iteration advances all live decode
    slots one token AND one mid-prefill slot by one chunk (falling back
    to a chunk-only step while no slot is actively decoding). Mid-prefill
    slots are flagged on the batcher (``mark_prefilling``) so no decode
    garbage lands in their ledger and ``t_first`` stamps on the first
    *generated* token. Ring (sliding-window) caches clamp the chunk to
    the cache length. Per-request streams stay bit-identical to the
    whole-prompt path (tests/test_serve_conformance.py).

    ``prefix_cache`` (a ``repro.serve.PrefixCache``) adds prefix KV
    reuse on top: at every chunk boundary the slot's cache rows are
    snapshotted under the prompt-prefix hash, and an arriving prompt
    whose prefix is cached splices the snapshot in (``splice_into``) and
    skips those chunks — a full-prompt hit skips prefill entirely.
    Implies chunked prefill (chunk defaults to ``prompt_len``). Hit /
    miss / eviction / tokens-skipped counters and a bytes gauge land in
    ``metrics``; capture/splice get tracer spans.

    ``arrivals`` (callable ``iteration -> bool``) injects open-loop
    request arrivals: called once per loop iteration (submitting to the
    batcher as it sees fit) and returning True while more requests may
    still arrive — the loop then idles through empty iterations instead
    of returning (serve_bench's staggered-arrival rows).
    """
    if temperature > 0.0 and key is None:
        raise ValueError("temperature-sampled serving requires a PRNG key")
    cap = max_new_cap
    if cap is None:
        pending = [r.max_new for r in batcher.queue]
        if not pending:
            return 0
        cap = max(pending)
    tr = engine.tracer
    step_h = queue_h = occ_h = None
    if metrics is not None:
        step_h = metrics.histogram("serve_step_seconds",
                                   "wall seconds per serving-loop step")
        queue_h = metrics.histogram("serve_queue_depth",
                                    "queued requests, sampled per step")
        occ_h = metrics.histogram("serve_slot_occupancy",
                                  "active-slot fraction, sampled per step")
    use_prefill_chunks = prefill_chunk > 0 or prefix_cache is not None
    if use_prefill_chunks and engine._replicas is not None:
        raise NotImplementedError(
            "chunked prefill / prefix reuse is single-sample only; drop "
            "prefill_chunk=/prefix_cache= for K-replica ensemble serving")
    chunk_len = prefill_chunk if prefill_chunk > 0 else batcher.prompt_len
    if use_prefill_chunks and engine.cfg.sliding_window:
        # ring caches need chunk <= cache length: chunk_attention's
        # post-attention ring write assigns each chunk token its own slot
        from repro.models.attention import cache_length
        chunk_len = min(chunk_len, cache_length(engine.cfg,
                                                batcher.prompt_len + cap))
    if prefix_cache is not None:
        # salt keys with the serving geometry (and this engine's identity):
        # snapshots from a different engine, context geometry or chunking
        # must never splice in — chunked and whole prefills agree only to
        # ulp order, so chunk size is part of the key
        prefix_cache.bind_geometry(
            f"{id(engine)}:{engine.cfg.family}:{engine.cfg.vocab_size}:"
            f"{batcher.prompt_len}:{cap}:{chunk_len}")
    pc_start = prefix_cache.stats() if prefix_cache is not None else None
    in_prefill: dict[int, int] = {}   # slot -> prompt tokens already in

    def _advance_prefill(state, slot, new_off):
        """Bookkeeping after a chunk landed: snapshot the chunk boundary
        into the prefix cache, and promote the slot to the active decode
        set once the whole prompt is in."""
        req = batcher.slots[slot]
        full = new_off >= batcher.prompt_len
        if prefix_cache is not None and (prefix_cache.store_partial or full):
            one, lg = engine.capture_slot(state, slot)
            prefix_cache.put(req.prompt[:new_off], one,
                             logits=lg if full else None)
        if full:
            batcher.mark_ready(slot)
            req.ready_step = iterations
            del in_prefill[slot]
        else:
            in_prefill[slot] = new_off

    t_start = time.perf_counter()
    steps = 0
    iterations = 0
    use_chunks = (decode_chunk > 1 and temperature == 0.0
                  and engine._replicas is None)
    with tr.span("stream_serve", n_slots=batcher.n_slots, cap=cap):
        with tr.span("init_decode"):
            state = engine.init_decode(batcher.n_slots, batcher.prompt_len,
                                       cap)
        try:
            while True:
                t_step = time.perf_counter()
                iterations += 1
                more_arrivals = False
                if arrivals is not None:
                    with tr.span("arrivals"):
                        more_arrivals = bool(arrivals(iterations))
                with tr.span("step", step=steps):
                    with tr.span("refill"):
                        for slot in batcher.refill():
                            req = batcher.slots[slot]
                            if req.max_new > cap:
                                raise ValueError(
                                    f"request {req.uid} wants max_new="
                                    f"{req.max_new} but the decode state was "
                                    f"sized for max_new_cap={cap}")
                            req.admit_step = iterations
                            if metrics is not None:
                                metrics.counter(
                                    "serve_prefills_total",
                                    "slot prefills (one per request "
                                    "admitted)").inc()
                            if not use_prefill_chunks:
                                state = engine.prefill_into(state, slot,
                                                            req.prompt)
                                req.ready_step = iterations
                                req.prefill_chunks = 1
                                continue
                            off = 0
                            if prefix_cache is not None:
                                hit = prefix_cache.lookup(req.prompt,
                                                          chunk_len)
                                if hit is not None:
                                    off, entry = hit
                                    full = off >= batcher.prompt_len
                                    state = engine.splice_into(
                                        state, slot, entry.cache,
                                        logits_row=entry.logits
                                        if full else None)
                            if off < batcher.prompt_len:
                                batcher.mark_prefilling(slot)
                                in_prefill[slot] = off
                            else:
                                req.ready_step = iterations
                    if metrics is not None:
                        queue_h.observe(len(batcher.queue))
                        occ_h.observe(
                            float(np.mean(batcher.active_mask())))
                    if batcher.idle:
                        if more_arrivals:
                            continue
                        return steps
                    if use_prefill_chunks and in_prefill:
                        # chunked-prefill scheduling: fuse one chunk of the
                        # oldest mid-prefill slot into the decode step when
                        # anything is decoding, else run the chunk alone
                        slot = next(iter(in_prefill))
                        off = in_prefill[slot]
                        req = batcher.slots[slot]
                        c = min(chunk_len, batcher.prompt_len - off)
                        chunk_toks = req.prompt[off:off + c]
                        if batcher.active_mask().any():
                            with tr.span("sample"):
                                if temperature > 0.0:
                                    key, sub = jax.random.split(key)
                                    tok = jax.random.categorical(
                                        sub,
                                        state.logits.astype(jnp.float32)
                                        / temperature, axis=-1)
                                else:
                                    tok = jnp.argmax(state.logits, axis=-1)
                                with tr.span("token_sync"):
                                    tok_host = np.asarray(tok)
                            with tr.span("record"):
                                batcher.record(tok_host)
                            steps += 1
                            if metrics is not None:
                                metrics.counter(
                                    "serve_steps_total",
                                    "token-emission steps").inc()
                            keep = np.array(
                                [i in batcher.prefilling
                                 for i in range(batcher.n_slots)])
                            state = engine.fused_step(state, tok, keep,
                                                      slot, chunk_toks, off)
                        else:
                            state = engine.prefill_chunk_into(
                                state, slot, chunk_toks, off)
                        req.prefill_chunks += 1
                        _advance_prefill(state, slot, off + c)
                        if metrics is not None:
                            metrics.counter("serve_prefill_chunks_total",
                                            "prefill chunks executed").inc()
                        if sentinel is not None:
                            sentinel.step()
                        if step_h is not None:
                            step_h.observe(time.perf_counter() - t_step)
                        continue
                    if use_chunks:
                        d = min(decode_chunk, batcher.min_remaining())
                        with tr.span("chunk", d=d):
                            state, toks = engine.decode_steps(state, d)
                            # the chunk's ONE host crossing (explicit, so a
                            # jax.transfer_guard around the steady state
                            # stays silent — asserted in tests)
                            with tr.span("token_sync"):
                                tok_chunk = jax.device_get(toks)
                        with tr.span("record"):
                            for i in range(d):
                                batcher.record(tok_chunk[:, i])
                        steps += d
                        if sentinel is not None:
                            sentinel.step()
                        if metrics is not None:
                            metrics.counter("serve_steps_total",
                                            "token-emission steps").inc(d)
                        if batcher.idle:
                            batcher.refill()
                        if step_h is not None:
                            step_h.observe(time.perf_counter() - t_step)
                        if batcher.idle and not more_arrivals:
                            return steps
                        continue
                    with tr.span("sample"):
                        if temperature > 0.0:
                            key, sub = jax.random.split(key)
                            tok = jax.random.categorical(
                                sub,
                                state.logits.astype(jnp.float32)
                                / temperature, axis=-1)
                        else:
                            tok = jnp.argmax(state.logits, axis=-1)
                        with tr.span("token_sync"):
                            tok_host = np.asarray(tok)
                    with tr.span("record"):
                        if state.agreement is not None:
                            with tr.span("token_sync"):
                                agr = np.asarray(state.agreement)
                                var = np.asarray(state.variance)
                            thr = engine.abstain_threshold
                            batcher.record(
                                tok_host, agreement=agr, variance=var,
                                abstained=None if thr is None
                                else agr < thr)
                        else:
                            batcher.record(tok_host)
                    steps += 1
                    if metrics is not None:
                        metrics.counter("serve_steps_total",
                                        "token-emission steps").inc()
                    if batcher.idle:
                        # flush the final completions; the trailing
                        # decode_step would be pure waste
                        batcher.refill()
                        if step_h is not None:
                            step_h.observe(time.perf_counter() - t_step)
                        if not more_arrivals:
                            return steps
                        continue
                    state = engine.decode_step(state, tok)
                    if sentinel is not None:
                        sentinel.step()
                if step_h is not None:
                    step_h.observe(time.perf_counter() - t_step)
        finally:
            if metrics is not None:
                from repro.obs.metrics import record_request_metrics

                record_request_metrics(metrics, batcher)
                if prefix_cache is not None:
                    pc = prefix_cache.stats()
                    metrics.counter(
                        "serve_prefix_hits_total",
                        "prefix-cache hits (prefill chunks skipped)").inc(
                        pc["hits"] - pc_start["hits"])
                    metrics.counter(
                        "serve_prefix_misses_total",
                        "prefix-cache misses (cold prefills)").inc(
                        pc["misses"] - pc_start["misses"])
                    metrics.counter(
                        "serve_prefix_evictions_total",
                        "prefix-cache LRU evictions").inc(
                        pc["evictions"] - pc_start["evictions"])
                    metrics.counter(
                        "serve_prefix_tokens_skipped_total",
                        "prompt tokens served from cached prefixes").inc(
                        pc["tokens_skipped"] - pc_start["tokens_skipped"])
                    metrics.gauge(
                        "serve_prefix_bytes",
                        "prefix-cache resident bytes").set(pc["bytes"])
                dt = time.perf_counter() - t_start
                if dt > 0:
                    metrics.gauge(
                        "serve_tok_per_s",
                        "recorded tokens / serving wall seconds").set(
                        batcher.tokens_generated / dt)
