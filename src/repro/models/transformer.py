"""Decoder stacks for all assigned LM-family architectures.

Three templates cover the pool:
  * ``uniform``  — every layer attention + FFN (dense or MoE):
                   starcoder2, qwen2.5, danube, deepseek, moonshot, grok,
                   musicgen, internvl2 backbones;
  * ``ssm``      — every layer a Mamba2 mixer: mamba2-130m;
  * ``hybrid``   — scan over periods of ``attn_period`` layers with one
                   attention layer per period and MoE on alternating layers:
                   jamba-1.5-large.

Layers are stacked on a leading axis and iterated with ``lax.scan`` so the
HLO stays O(1) in depth (fast multi-pod compiles, clean roofline attribution).
Forward passes are binarization-agnostic (see models/layers.py).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import mlp as M
from repro.models import moe as MOE
from repro.models import ssm as S
from repro.models.layers import embed_lookup, lm_init, rms_norm


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stacked(init_one, key, n: int):
    return jax.vmap(init_one)(jax.random.split(key, n))


def init_lm(cfg, key, dtype=jnp.float32) -> dict:
    """Parameter tree with every leaf drawn in float32 (the training
    masters). A narrower ``dtype`` (serving: ``cfg.activation_dtype``)
    holds the same draws cast to it; the draw and the cast run in one jit,
    so the float32 tree is never resident."""
    if jnp.dtype(dtype) != jnp.float32:
        return jax.jit(lambda k: jax.tree.map(
            lambda a: a.astype(dtype), init_lm(cfg, k)))(key)
    keys = jax.random.split(key, 8)
    params: dict[str, Any] = {
        "embed": {"embedding": lm_init(keys[0], (cfg.vocab_size, cfg.d_model),
                                       fan_in=cfg.d_model)},
        "final_norm": {"scale": jnp.zeros((cfg.d_model,))},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": lm_init(keys[1], (cfg.d_model, cfg.vocab_size))}

    if cfg.family == "ssm":
        params["layers"] = {
            "ssm": _stacked(lambda k: S.init_ssm(k, cfg, lm_init), keys[2], cfg.n_layers),
            "ln1": {"scale": jnp.zeros((cfg.n_layers, cfg.d_model))},
        }
        return params

    if cfg.is_hybrid:
        per = cfg.attn_period
        n_per = cfg.n_layers // per
        n_mamba = per - 1
        n_moe = sum(cfg.moe_layer(i) for i in range(per))
        n_dense = per - n_moe
        params["layers"] = {
            "attn": _stacked(lambda k: A.init_attn(k, cfg, lm_init), keys[2], n_per),
            "mamba": jax.vmap(lambda ks: _stacked(
                lambda k: S.init_ssm(k, cfg, lm_init), ks, n_mamba))(
                jax.random.split(keys[3], n_per)),
            "mlp": jax.vmap(lambda ks: _stacked(
                lambda k: M.init_mlp(k, cfg, lm_init), ks, n_dense))(
                jax.random.split(keys[4], n_per)),
            "moe": jax.vmap(lambda ks: _stacked(
                lambda k: MOE.init_moe(k, cfg, lm_init), ks, n_moe))(
                jax.random.split(keys[5], n_per)),
            "ln1": {"scale": jnp.zeros((n_per, per, cfg.d_model))},
            "ln2": {"scale": jnp.zeros((n_per, per, cfg.d_model))},
        }
        return params

    # uniform
    layer_p = {
        "attn": _stacked(lambda k: A.init_attn(k, cfg, lm_init), keys[2], cfg.n_layers),
        "ln1": {"scale": jnp.zeros((cfg.n_layers, cfg.d_model))},
        "ln2": {"scale": jnp.zeros((cfg.n_layers, cfg.d_model))},
    }
    if cfg.n_experts and cfg.moe_every == 1:
        layer_p["moe"] = _stacked(lambda k: MOE.init_moe(k, cfg, lm_init),
                                  keys[3], cfg.n_layers)
    else:
        layer_p["mlp"] = _stacked(lambda k: M.init_mlp(k, cfg, lm_init),
                                  keys[3], cfg.n_layers)
    params["layers"] = layer_p
    return params


# ---------------------------------------------------------------------------
# forward (training / scoring): tokens or embeds -> logits
# ---------------------------------------------------------------------------

def _maybe_remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_saveable)
    return jax.checkpoint(fn)


# A/B measured in EXPERIMENTS.md §Perf iteration 4: nested per-sublayer
# checkpoints ADDED 18% recompute FLOPs and 10 GB peak on jamba train
# (XLA's buffer assignment does not exploit the finer structure under the
# outer scan remat), so outer-body remat only is the default.
SUB_REMAT = False


def _sub_remat(fn, cfg):
    """Per-SUBLAYER remat nested inside the outer scan-body remat: the
    backward recomputes one sublayer at a time, bounding the live set to one
    sublayer's internals + the (sequence-parallel, small) residuals.
    Measured against outer-only remat in EXPERIMENTS.md §Perf iteration 4."""
    if cfg.remat == "none" or not SUB_REMAT:
        return fn
    return jax.checkpoint(fn)


def _embed_in(cfg, params, tokens_or_embeds, sh):
    if tokens_or_embeds.dtype in (jnp.int32, jnp.int64):
        x = embed_lookup(params["embed"]["embedding"], tokens_or_embeds,
                         cfg.activation_dtype)
    else:
        x = tokens_or_embeds.astype(cfg.activation_dtype)  # stubbed frontend
    return sh.act(x, "btd") if sh is not None else x


def _head_out(cfg, params, x, sh):
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"]["scale"])
        if cfg.tie_embeddings:
            w = params["embed"]["embedding"].astype(x.dtype).T
        else:
            w = params["lm_head"]["kernel"].astype(x.dtype)
        logits = jnp.dot(x, w)
        return sh.act(logits, "btv") if sh is not None else logits


def _decode_head_out(cfg, params, x, sh):
    """Decode head: col-parallel logits matmul + ONE deferred gather.

    The "btv" constraint inside :func:`_head_out` keeps the dot's output
    vocab-sharded (weight-stationary — pinning it replicated makes GSPMD
    all-gather the whole tied-embedding table instead of the logits), and
    the "bv" constraint here is the single small (B, V) gather the whole
    decode step defers to."""
    logits = _head_out(cfg, params, x, sh)[:, -1]
    return sh.act(logits, "bv") if sh is not None else logits


def forward(cfg, params, tokens_or_embeds, sh=None):
    """Full-sequence forward -> (logits, aux)."""
    x = _embed_in(cfg, params, tokens_or_embeds, sh)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)

    if cfg.family == "ssm":
        ssm_fn = _sub_remat(lambda p, h: S.ssm_forward(cfg, p, h, sh), cfg)

        def body(carry, lp):
            x = carry
            h = rms_norm(x, lp["ln1"]["scale"])
            x = x + ssm_fn(lp["ssm"], h)
            return sh.act(x, "btd") if sh is not None else x, ()

        x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, params["layers"])
        return _head_out(cfg, params, x, sh), {"lb_loss": jnp.float32(0)}

    if cfg.is_hybrid:
        x, aux = _hybrid_scan(cfg, params, x, positions, sh)
        return _head_out(cfg, params, x, sh), aux

    attn_fn = _sub_remat(
        lambda p, h: A.attention(cfg, p, h, positions, sh), cfg)
    mlp_fn = _sub_remat(lambda p, h: M.mlp(cfg, p, h, sh), cfg)
    moe_fn = _sub_remat(lambda p, h: MOE.moe_ffn(cfg, p, h, sh), cfg)

    def body(carry, lp):
        x, lb = carry
        h = rms_norm(x, lp["ln1"]["scale"])
        x = x + attn_fn(lp["attn"], h)
        h = rms_norm(x, lp["ln2"]["scale"])
        if "moe" in lp:
            y, aux = moe_fn(lp["moe"], h)
            lb = lb + aux["lb_loss"]
        else:
            y = mlp_fn(lp["mlp"], h)
        x = x + y
        return ((sh.act(x, "btd") if sh is not None else x), lb), ()

    (x, lb), _ = jax.lax.scan(_maybe_remat(body, cfg),
                              (x, jnp.float32(0)), params["layers"])
    return _head_out(cfg, params, x, sh), {"lb_loss": lb}


def _hybrid_scan(cfg, params, x, positions, sh):
    per = cfg.attn_period
    attn_at = per // 2
    attn_fn = _sub_remat(
        lambda p, h: A.attention(cfg, p, h, positions, sh), cfg)
    ssm_fn = _sub_remat(lambda p, h: S.ssm_forward(cfg, p, h, sh), cfg)
    mlp_fn = _sub_remat(lambda p, h: M.mlp(cfg, p, h, sh), cfg)
    moe_fn = _sub_remat(lambda p, h: MOE.moe_ffn(cfg, p, h, sh), cfg)

    def body(carry, lp):
        x, lb = carry
        mi = di = oi = 0
        for j in range(per):
            h = rms_norm(x, lp["ln1"]["scale"][j])
            if j == attn_at:
                x = x + attn_fn(lp["attn"], h)
            else:
                mamba_j = jax.tree.map(lambda a, i=mi: a[i], lp["mamba"])
                x = x + ssm_fn(mamba_j, h)
                mi += 1
            h = rms_norm(x, lp["ln2"]["scale"][j])
            if cfg.moe_layer(j):
                moe_j = jax.tree.map(lambda a, i=oi: a[i], lp["moe"])
                y, aux = moe_fn(moe_j, h)
                lb = lb + aux["lb_loss"]
                oi += 1
            else:
                mlp_j = jax.tree.map(lambda a, i=di: a[i], lp["mlp"])
                y = mlp_fn(mlp_j, h)
                di += 1
            x = x + y
            if sh is not None:
                x = sh.act(x, "btd")
        return (x, lb), ()

    (x, lb), _ = jax.lax.scan(_maybe_remat(body, cfg),
                              (x, jnp.float32(0)), params["layers"])
    return x, {"lb_loss": lb}


# ---------------------------------------------------------------------------
# decode cache
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, context_len: int, dtype=None) -> dict:
    """Zeroed decode cache for a context of ``context_len`` tokens."""
    dtype = dtype or cfg.activation_dtype
    s_kv = A.cache_length(cfg, context_len)
    cache: dict[str, Any] = {"pos": jnp.zeros((batch,), jnp.int32)}
    conv_dim = cfg.d_inner + 2 * cfg.ssm_state
    if cfg.family == "ssm":
        cache["ssm"] = jnp.zeros(
            (cfg.n_layers, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            jnp.float32)
        cache["conv"] = jnp.zeros(
            (cfg.n_layers, batch, cfg.ssm_conv_width - 1, conv_dim), dtype)
        return cache
    if cfg.is_hybrid:
        n_per = cfg.n_layers // cfg.attn_period
        nm = cfg.attn_period - 1
        cache["k"] = jnp.zeros((n_per, batch, s_kv, cfg.n_kv_heads, cfg.head_dim), dtype)
        cache["v"] = jnp.zeros_like(cache["k"])
        cache["ssm"] = jnp.zeros(
            (n_per, nm, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            jnp.float32)
        cache["conv"] = jnp.zeros(
            (n_per, nm, batch, cfg.ssm_conv_width - 1, conv_dim), dtype)
        return cache
    cache["k"] = jnp.zeros(
        (cfg.n_layers, batch, s_kv, cfg.n_kv_heads, cfg.head_dim), dtype)
    cache["v"] = jnp.zeros_like(cache["k"])
    return cache


def cache_slot_axes(cfg) -> dict[str, int]:
    """Slot (batch) axis of every decode-cache entry for this family.

    The decode cache is a long-lived, slot-addressed structure under
    continuous batching: each request owns one index along these axes for
    its lifetime, and ``cache_insert`` splices a freshly prefilled request
    in without touching the other slots."""
    if cfg.family == "ssm":
        return {"pos": 0, "ssm": 1, "conv": 1}
    if cfg.is_hybrid:
        return {"pos": 0, "k": 1, "v": 1, "ssm": 2, "conv": 2}
    return {"pos": 0, "k": 1, "v": 1}


def cache_pspecs(cfg, dp_axes=("data",)) -> dict:
    """PartitionSpec per decode-cache entry: slots (the continuous-batching
    batch dim) shard over the data axes; every other axis — in particular
    the KV sequence — is *replicated* over "model" (matching the serving
    ``cache_kv`` / ``ssm_state`` kinds of a ``decode=True``
    ``repro.distributed.sharding.ShardCtx``). Replicating the sequence axis
    trades per-device cache bytes for copy-free updates: the per-step
    ``.at[slot, pos].set`` write and ``cache_insert`` splice are then
    device-local scatters into a donated buffer, where the earlier
    seq-over-"model" flash-decoding layout cost ~10 collectives + reshard
    copies per decode step (measured in ``benchmarks/golden_plans/
    collectives.json`` before/after — see docs/ARCHITECTURE.md §Decode-step
    collective budget). Keyed like :func:`cache_slot_axes`; used by
    ``ServeEngine.init_decode`` to place the persistent
    :class:`~repro.serve.engine.DecodeState` on a mesh. ``dp_axes`` may be
    empty (a pure tensor-parallel mesh with no data axis): the whole cache
    then replicates. Specs shorter than an entry's rank replicate the
    trailing dims."""
    from jax.sharding import PartitionSpec as P

    dp = (tuple(dp_axes) if len(dp_axes) > 1
          else dp_axes[0] if dp_axes else None)
    if cfg.family == "ssm":
        return {"pos": P(dp),
                "ssm": P(None, dp),        # (L, B, H, hp, N)
                "conv": P(None, dp)}       # (L, B, w-1, conv_dim)
    if cfg.is_hybrid:
        return {"pos": P(dp),
                "k": P(None, dp),          # (n_per, B, S, kv, hd)
                "v": P(None, dp),
                "ssm": P(None, None, dp),  # (n_per, nm, B, H, ...)
                "conv": P(None, None, dp)}
    return {"pos": P(dp),
            "k": P(None, dp),              # (L, B, S, kv, hd)
            "v": P(None, dp)}


def cache_insert(cfg, cache: dict, one: dict, slot) -> dict:
    """Insert a batch-1 cache ``one`` into ``cache`` at slot index ``slot``.

    ``one`` must come from a prefill with the same ``max_len`` (so the
    context axes already agree); ``slot`` may be a traced int32 scalar —
    all shapes are static, so a jitted caller never re-specializes on the
    slot index. Returns the updated cache (other slots untouched)."""
    axes = cache_slot_axes(cfg)
    if set(axes) != set(cache):
        raise ValueError(
            f"cache_slot_axes is out of sync with the cache layout: axes "
            f"cover {sorted(axes)}, cache has {sorted(cache)} — an entry "
            f"left out would silently keep the slot's previous occupant")
    out = dict(cache)
    for name, axis in axes.items():
        upd = one[name].astype(cache[name].dtype)
        if upd.shape[axis] != 1:
            raise ValueError(
                f"cache_insert expects a batch-1 cache; {name!r} has "
                f"{upd.shape[axis]} slots on axis {axis}")
        out[name] = jax.lax.dynamic_update_slice_in_dim(
            cache[name], upd, slot, axis=axis)
    return out


def cache_extract(cfg, cache: dict, slot) -> dict:
    """Batch-1 snapshot of one slot's cache rows (inverse of
    :func:`cache_insert`). ``slot`` may be traced; shapes are static."""
    axes = cache_slot_axes(cfg)
    return {name: jax.lax.dynamic_slice_in_dim(cache[name], slot, 1, axis=ax)
            for name, ax in axes.items()}


def cache_keep(cfg, old: dict, new: dict, keep) -> dict:
    """Per-slot merge of two caches: slots where ``keep`` (bool (n_slots,))
    is True retain ``old``'s rows, the rest take ``new``.

    This is what makes a partially-prefilled slot survive the fused
    decode+prefill step: a plain ``decode_step`` advances every slot's
    state, so the fused step re-selects the old rows for mid-prefill slots
    before the chunk runs. Only state a pending chunk cannot rewrite is
    re-selected — the position counters (pinning ``pos`` stops the
    per-step climb, confining the foreign decode's K/V write to the one
    index the slot's next chunk overwrites before anything reads it; the
    chunk masks by its host-tracked offset and sets ``pos`` absolutely)
    and the recurrent ``ssm``/``conv`` states (a multiplicative update, so
    a foreign decode corrupts them irreversibly). Append-style K/V
    buffers pass through untouched: a full-cache ``jnp.where`` would keep
    both copies alive and force XLA to materialize the whole cache every
    fused step, costing more than the prefill chunk itself. Selection is
    elementwise (bit-exact, GSPMD-local)."""
    axes = cache_slot_axes(cfg)
    out = dict(new)
    for name, axis in axes.items():
        if name not in ("pos", "ssm", "conv"):
            continue
        shape = [1] * old[name].ndim
        shape[axis] = old[name].shape[axis]
        out[name] = jnp.where(keep.reshape(shape), old[name], new[name])
    return out


def _layer_ids(cache):
    """Scan input of layer indices into the stacked K/V cache. The cache
    itself rides in the scan carry, so each layer writes its new rows into
    the one buffer in place, where a cache passed as scan input and output
    would be restacked, every layer's slab into a fresh buffer."""
    return jnp.arange(cache["k"].shape[0], dtype=jnp.int32)


def _set_pos(pos, slot, value):
    upd = jnp.reshape(value, (1,)).astype(pos.dtype)
    return jax.lax.dynamic_update_slice_in_dim(pos, upd, slot, axis=0)


def prefill_chunk(cfg, params, cache: dict, tokens, slot, offset, sh=None):
    """Advance ONE slot's prefill by a chunk of C prompt tokens.

    tokens: (1, C) int32 with C static; ``slot`` / ``offset`` are traced
    int32 scalars, ``offset`` the number of prompt tokens already in the
    slot. The partially-prefilled slot is a first-class cache state for
    every family: attention reads the slot's pre-write rows and masks
    exactly what a whole-prompt prefill would see (ring-aware for sliding
    windows), ssm/hybrid thread the slot's recurrent + conv states through
    the chunk. Returns (last-token logits (1, V), new_cache) with
    ``cache["pos"][slot]`` advanced to ``offset + C``."""
    x = _embed_in(cfg, params, tokens, sh)
    c = x.shape[1]
    new_pos = _set_pos(cache["pos"], slot, offset + c)

    if cfg.family == "ssm":
        st0 = jax.lax.dynamic_slice_in_dim(cache["ssm"], slot, 1, axis=1)
        cv0 = jax.lax.dynamic_slice_in_dim(cache["conv"], slot, 1, axis=1)
        # offset == 0 is a FRESH prefill: the slot's resident state belongs
        # to its previous occupant and must read as start-of-sequence zeros
        # (attention needs no gate — masking zeroes stale lanes exactly)
        st0 = jnp.where(offset > 0, st0, jnp.zeros_like(st0))
        cv0 = jnp.where(offset > 0, cv0, jnp.zeros_like(cv0))

        def body(x, xs):
            lp, st, cv = xs
            h = rms_norm(x, lp["ln1"]["scale"])
            y, st, cv = S.ssm_forward(cfg, lp["ssm"], h, sh, chunk=c,
                                      return_state=True,
                                      initial_state=st, conv_state=cv)
            return x + y, (st, cv)

        x, (sts, cvs) = jax.lax.scan(
            body, x, (params["layers"], st0, cv0))
        new_cache = dict(
            cache,
            ssm=jax.lax.dynamic_update_slice_in_dim(
                cache["ssm"], sts.astype(cache["ssm"].dtype), slot, axis=1),
            conv=jax.lax.dynamic_update_slice_in_dim(
                cache["conv"], cvs.astype(cache["conv"].dtype), slot, axis=1),
            pos=new_pos)
        return _decode_head_out(cfg, params, x[:, -1:], sh), new_cache

    if cfg.is_hybrid:
        return _hybrid_prefill_chunk(cfg, params, cache, x, slot, offset,
                                     new_pos, sh)

    def body(carry, xs):
        x, kc, vc = carry
        lp, layer = xs
        h = rms_norm(x, lp["ln1"]["scale"])
        y, kc, vc = A.chunk_attention(cfg, lp["attn"], h, kc, vc, layer,
                                      slot, offset, sh)
        x = x + y
        h = rms_norm(x, lp["ln2"]["scale"])
        if "moe" in lp:
            y, _ = MOE.moe_ffn(cfg, lp["moe"], h, sh)
        else:
            y = M.mlp(cfg, lp["mlp"], h, sh)
        return (x + y, kc, vc), ()

    (x, new_k, new_v), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["layers"], _layer_ids(cache)))
    new_cache = dict(cache, k=new_k, v=new_v, pos=new_pos)
    return _decode_head_out(cfg, params, x[:, -1:], sh), new_cache


def _hybrid_prefill_chunk(cfg, params, cache, x, slot, offset, new_pos, sh):
    per = cfg.attn_period
    attn_at = per // 2
    c = x.shape[1]

    def body(carry, xs):
        x, kc, vc = carry
        lp, layer, stc, cvc = xs
        st_s = jax.lax.dynamic_slice_in_dim(stc, slot, 1, axis=1)
        cv_s = jax.lax.dynamic_slice_in_dim(cvc, slot, 1, axis=1)
        # fresh prefill (offset == 0): stale occupant state reads as zeros
        st_s = jnp.where(offset > 0, st_s, jnp.zeros_like(st_s))
        cv_s = jnp.where(offset > 0, cv_s, jnp.zeros_like(cv_s))
        mi = di = oi = 0
        new_st, new_cv = [], []
        for j in range(per):
            h = rms_norm(x, lp["ln1"]["scale"][j])
            if j == attn_at:
                y, kc, vc = A.chunk_attention(cfg, lp["attn"], h, kc, vc,
                                              layer, slot, offset, sh)
            else:
                mamba_j = jax.tree.map(lambda a, i=mi: a[i], lp["mamba"])
                y, st, cv = S.ssm_forward(cfg, mamba_j, h, sh, chunk=c,
                                          return_state=True,
                                          initial_state=st_s[mi],
                                          conv_state=cv_s[mi])
                new_st.append(st)
                new_cv.append(cv)
                mi += 1
            x = x + y
            h = rms_norm(x, lp["ln2"]["scale"][j])
            if cfg.moe_layer(j):
                moe_j = jax.tree.map(lambda a, i=oi: a[i], lp["moe"])
                y, _ = MOE.moe_ffn(cfg, moe_j, h, sh)
                oi += 1
            else:
                mlp_j = jax.tree.map(lambda a, i=di: a[i], lp["mlp"])
                y = M.mlp(cfg, mlp_j, h, sh)
                di += 1
            x = x + y
        stc = jax.lax.dynamic_update_slice_in_dim(
            stc, jnp.stack(new_st).astype(stc.dtype), slot, axis=1)
        cvc = jax.lax.dynamic_update_slice_in_dim(
            cvc, jnp.stack(new_cv).astype(cvc.dtype), slot, axis=1)
        return (x, kc, vc), (stc, cvc)

    (x, nk, nv), (nst, ncv) = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["layers"], _layer_ids(cache), cache["ssm"], cache["conv"]))
    new_cache = dict(cache, k=nk, v=nv, ssm=nst, conv=ncv, pos=new_pos)
    return _decode_head_out(cfg, params, x[:, -1:], sh), new_cache


def decode_step(cfg, params, cache: dict, tokens_or_embeds, sh=None):
    """One decode step for the whole batch -> (logits, new_cache).

    tokens: (B, 1) int32 (or (B, 1, D) stub embeddings)."""
    x = _embed_in(cfg, params, tokens_or_embeds, sh)
    pos = cache["pos"]

    if cfg.family == "ssm":
        def body(x, xs):
            lp, st, cv = xs
            h = rms_norm(x, lp["ln1"]["scale"])
            y, st, cv = S.ssm_decode_step(cfg, lp["ssm"], h, st, cv)
            return x + y, (st, cv)

        x, (new_ssm, new_conv) = jax.lax.scan(
            body, x, (params["layers"], cache["ssm"], cache["conv"]))
        new_cache = dict(cache, ssm=new_ssm, conv=new_conv, pos=pos + 1)
        return _decode_head_out(cfg, params, x, sh), new_cache

    if cfg.is_hybrid:
        return _hybrid_decode(cfg, params, cache, x, sh)

    def body(carry, xs):
        x, kc, vc = carry
        lp, layer = xs
        h = rms_norm(x, lp["ln1"]["scale"])
        y, kc, vc = A.decode_attention(cfg, lp["attn"], h, kc, vc, layer,
                                       pos, sh)
        x = x + y
        h = rms_norm(x, lp["ln2"]["scale"])
        if "moe" in lp:
            y, _ = MOE.moe_ffn(cfg, lp["moe"], h, sh)
        else:
            y = M.mlp(cfg, lp["mlp"], h, sh)
        return (x + y, kc, vc), ()

    (x, new_k, new_v), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["layers"], _layer_ids(cache)))
    new_cache = dict(cache, k=new_k, v=new_v, pos=pos + 1)
    return _decode_head_out(cfg, params, x, sh), new_cache


def _hybrid_decode(cfg, params, cache, x, sh):
    per = cfg.attn_period
    attn_at = per // 2
    pos = cache["pos"]

    def body(carry, xs):
        x, kc, vc = carry
        lp, layer, stc, cvc = xs
        mi = di = oi = 0
        new_st, new_cv = [], []
        for j in range(per):
            h = rms_norm(x, lp["ln1"]["scale"][j])
            if j == attn_at:
                y, kc, vc = A.decode_attention(cfg, lp["attn"], h, kc, vc,
                                               layer, pos, sh)
            else:
                mamba_j = jax.tree.map(lambda a, i=mi: a[i], lp["mamba"])
                y, st, cv = S.ssm_decode_step(cfg, mamba_j, h, stc[mi], cvc[mi])
                new_st.append(st)
                new_cv.append(cv)
                mi += 1
            x = x + y
            h = rms_norm(x, lp["ln2"]["scale"][j])
            if cfg.moe_layer(j):
                moe_j = jax.tree.map(lambda a, i=oi: a[i], lp["moe"])
                y, _ = MOE.moe_ffn(cfg, moe_j, h, sh)
                oi += 1
            else:
                mlp_j = jax.tree.map(lambda a, i=di: a[i], lp["mlp"])
                y = M.mlp(cfg, mlp_j, h, sh)
                di += 1
            x = x + y
        return (x, kc, vc), (jnp.stack(new_st), jnp.stack(new_cv))

    (x, nk, nv), (nst, ncv) = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["layers"], _layer_ids(cache), cache["ssm"], cache["conv"]))
    new_cache = dict(cache, k=nk, v=nv, ssm=nst, conv=ncv, pos=pos + 1)
    return _decode_head_out(cfg, params, x, sh), new_cache


# ---------------------------------------------------------------------------
# prefill: full context -> (last-token logits, populated cache)
# ---------------------------------------------------------------------------

def _to_cache_layout(cfg, k: jax.Array, s: int, s_kv: int) -> jax.Array:
    """(B, S, KV, hd) prefill keys -> ring/linear cache of length s_kv.

    Invariant shared with ``decode_attention``: token at absolute position
    ``p`` lives at slot ``p % s_kv`` (ring) for sliding-window archs, slot
    ``p`` (linear) otherwise."""
    if cfg.sliding_window and s > s_kv:
        k = k[:, -s_kv:]
        return jnp.roll(k, shift=(s - s_kv) % s_kv, axis=1)
    if s < s_kv:
        return jnp.pad(k, ((0, 0), (0, s_kv - s)) + ((0, 0),) * (k.ndim - 2))
    return k


def prefill(cfg, params, tokens_or_embeds, sh=None, max_len: int | None = None):
    """Prefill ``s`` context tokens; cache is sized for ``max_len`` total
    positions (default ``s + 1`` so at least one decode step fits)."""
    x = _embed_in(cfg, params, tokens_or_embeds, sh)
    bsz, s = x.shape[0], x.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)
    s_kv = A.cache_length(cfg, max_len if max_len is not None else s + 1)

    if cfg.family == "ssm":
        def body(x, lp):
            h = rms_norm(x, lp["ln1"]["scale"])
            y, st, cv = S.ssm_forward(cfg, lp["ssm"], h, sh, return_state=True)
            return x + y, (st, cv)

        x, (sts, cvs) = jax.lax.scan(body, x, params["layers"])
        cache = {"ssm": sts, "conv": cvs,
                 "pos": jnp.full((bsz,), s, jnp.int32)}
        return _head_out(cfg, params, x, sh)[:, -1], cache

    if cfg.is_hybrid:
        return _hybrid_prefill(cfg, params, x, positions, sh, max_len)

    def body(x, lp):
        h = rms_norm(x, lp["ln1"]["scale"])
        y, k, v = A.attention_with_cache_write(cfg, lp["attn"], h, positions, sh)
        x = x + y
        h = rms_norm(x, lp["ln2"]["scale"])
        if "moe" in lp:
            y, _ = MOE.moe_ffn(cfg, lp["moe"], h, sh)
        else:
            y = M.mlp(cfg, lp["mlp"], h, sh)
        return x + y, (_to_cache_layout(cfg, k.astype(cfg.activation_dtype), s, s_kv),
                       _to_cache_layout(cfg, v.astype(cfg.activation_dtype), s, s_kv))

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    if sh is not None:
        ks, vs = sh.act(ks, "cache_kv"), sh.act(vs, "cache_kv")
    cache = {"k": ks, "v": vs, "pos": jnp.full((bsz,), s, jnp.int32)}
    return _head_out(cfg, params, x, sh)[:, -1], cache


def _hybrid_prefill(cfg, params, x, positions, sh, max_len: int | None = None):
    per = cfg.attn_period
    attn_at = per // 2
    bsz, s = x.shape[0], x.shape[1]
    s_kv = A.cache_length(cfg, max_len if max_len is not None else s + 1)

    def body(x, lp):
        mi = di = oi = 0
        sts, cvs = [], []
        kout = vout = None
        for j in range(per):
            h = rms_norm(x, lp["ln1"]["scale"][j])
            if j == attn_at:
                y, k, v = A.attention_with_cache_write(cfg, lp["attn"], h, positions, sh)
                kout = _to_cache_layout(cfg, k.astype(cfg.activation_dtype), s, s_kv)
                vout = _to_cache_layout(cfg, v.astype(cfg.activation_dtype), s, s_kv)
            else:
                mamba_j = jax.tree.map(lambda a, i=mi: a[i], lp["mamba"])
                y, st, cv = S.ssm_forward(cfg, mamba_j, h, sh, return_state=True)
                sts.append(st)
                cvs.append(cv)
                mi += 1
            x = x + y
            h = rms_norm(x, lp["ln2"]["scale"][j])
            if cfg.moe_layer(j):
                moe_j = jax.tree.map(lambda a, i=oi: a[i], lp["moe"])
                y, _ = MOE.moe_ffn(cfg, moe_j, h, sh)
                oi += 1
            else:
                mlp_j = jax.tree.map(lambda a, i=di: a[i], lp["mlp"])
                y = M.mlp(cfg, mlp_j, h, sh)
                di += 1
            x = x + y
        return x, (kout, vout, jnp.stack(sts), jnp.stack(cvs))

    x, (ks, vs, sts, cvs) = jax.lax.scan(body, x, params["layers"])
    cache = {"k": ks, "v": vs, "ssm": sts, "conv": cvs,
             "pos": jnp.full((bsz,), s, jnp.int32)}
    return _head_out(cfg, params, x, sh)[:, -1], cache
