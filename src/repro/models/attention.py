"""Grouped-query attention with RoPE, sliding windows, and KV caching.

Covers the assigned families: GQA (all LM archs), MHA (musicgen kv==heads),
sliding-window (h2o-danube-3), QKV bias (qwen2.5), plus the decode path used
by ``serve_step`` (single new token against a cached context; under a
serving ``ShardCtx`` the cache is sharded batch-over-data only — sequence
replicated over "model" — so the per-step cache write, softmax and PV
reduction all run device-local, and the block's cross-device traffic is one
all-gather after the col-parallel qkv matmul plus one all-reduce for the
row-parallel output projection).

The decode and chunk paths take the whole stacked cache ``(L, B, S, KV,
hd)``, which the layer scan carries, and a layer index: each layer writes
only its new rows into the cache, in place (decode: one row per slot,
before attention reads the layer; chunk: the slot's C rows, after).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import apply_linear, apply_rope

NEG_INF = -1e30


class AttnParams(NamedTuple):
    w_qkv: jax.Array                 # (D, (H + 2*KV) * hd)
    w_o: jax.Array                   # (H * hd, D)
    b_qkv: Optional[jax.Array] = None


def init_attn(key, cfg, init_fn) -> dict:
    k1, k2 = jax.random.split(key)
    p = {
        "w_qkv": init_fn(k1, (cfg.d_model, cfg.q_dim + 2 * cfg.kv_dim)),
        "w_o": init_fn(k2, (cfg.q_dim, cfg.d_model)),
    }
    if cfg.qkv_bias:
        p["b_qkv"] = jnp.zeros((cfg.q_dim + 2 * cfg.kv_dim,), jnp.float32)
    return p


def _split_qkv(cfg, qkv):
    q, k, v = jnp.split(qkv, [cfg.q_dim, cfg.q_dim + cfg.kv_dim], axis=-1)
    b, s = q.shape[:2]
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def _repeat_kv(x: jax.Array, groups: int) -> jax.Array:
    if groups == 1:
        return x
    b, s, kv, hd = x.shape
    return jnp.repeat(x, groups, axis=2)


def causal_mask(s_q: int, s_k: int, window: Optional[int], q_offset: int = 0):
    """(s_q, s_k) boolean mask; True = attend. Supports sliding window."""
    qi = jnp.arange(s_q)[:, None] + q_offset
    ki = jnp.arange(s_k)[None, :]
    m = ki <= qi
    if window is not None:
        m &= (qi - ki) < window
    return m


# Sequences at or above this length use the chunked online-softmax (flash)
# path, which keeps attention memory O(S * chunk) instead of O(S^2).
FLASH_THRESHOLD = 4096
FLASH_CHUNK = 1024


def flash_attention(q, k, v, *, window: Optional[int] = None,
                    chunk_q: int = FLASH_CHUNK, chunk_k: int = FLASH_CHUNK):
    """Causal chunked attention with online softmax (pure jnp).

    q/k/v: (B, S, H, hd), k/v already GQA-expanded. Memory per step is one
    (B, H, cq, ck) block; masked blocks are computed-and-discarded (the
    waste is < 1% of a full model's FLOPs at 32k — see DESIGN/§Perf)."""
    bsz, s, h, hd = q.shape
    nq, nk = s // chunk_q, s // chunk_k
    scale = hd ** -0.5
    qc = jnp.moveaxis(q.reshape(bsz, nq, chunk_q, h, hd), 1, 0)
    kc = jnp.moveaxis(k.reshape(bsz, nk, chunk_k, h, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(bsz, nk, chunk_k, h, hd), 1, 0)
    qi = jnp.arange(chunk_q)
    kj = jnp.arange(chunk_k)

    def q_block(_, iq):
        i, qb = iq                                  # qb: (B, cq, H, hd)

        def k_block(carry, jk):
            m, l, acc = carry
            j, kb, vb = jk
            logits = jnp.einsum("bqhd,bkhd->bhqk", qb, kb)
            logits = logits.astype(jnp.float32) * scale
            qpos = i * chunk_q + qi[:, None]
            kpos = j * chunk_k + kj[None, :]
            msk = kpos <= qpos
            if window is not None:
                msk &= (qpos - kpos) < window
            logits = jnp.where(msk[None, None], logits, NEG_INF)
            m_new = jnp.maximum(m, logits.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(logits - m_new[..., None])
            l_new = l * alpha + p.sum(-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(qb.dtype), vb).astype(jnp.float32)
            return (m_new, l_new, acc_new), ()

        m0 = jnp.full((bsz, h, chunk_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((bsz, h, chunk_q), jnp.float32)
        a0 = jnp.zeros((bsz, h, chunk_q, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            k_block, (m0, l0, a0), (jnp.arange(nk), kc, vc))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return (), jnp.moveaxis(out, 1, 2).astype(qb.dtype)  # (B, cq, H, hd)

    _, ob = jax.lax.scan(q_block, (), (jnp.arange(nq), qc))
    return jnp.moveaxis(ob, 0, 1).reshape(bsz, s, h, hd)


def _sdpa(cfg, q, k, v, s: int):
    """Dispatch: dense attention below FLASH_THRESHOLD, flash above."""
    if s >= FLASH_THRESHOLD and s % FLASH_CHUNK == 0:
        return flash_attention(q, k, v, window=cfg.sliding_window)
    scale = cfg.head_dim ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    mask = causal_mask(s, s, cfg.sliding_window)
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention(cfg, params: dict, x: jax.Array, positions: jax.Array,
              sh=None) -> jax.Array:
    """Full (training / prefill) self-attention. x: (B, S, D)."""
    qkv = apply_linear(params["w_qkv"], x, params.get("b_qkv"),
                       sh=sh, kind="btq")
    q, k, v = _split_qkv(cfg, qkv)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    groups = cfg.n_heads // cfg.n_kv_heads
    k = _repeat_kv(k, groups)
    v = _repeat_kv(v, groups)
    if sh is not None:  # heads over "model" (padded when H % axis != 0)
        q, k, v = (sh.act(t, "bthd") for t in (q, k, v))

    out = _sdpa(cfg, q, k, v, x.shape[1])
    out = out.reshape(x.shape[0], x.shape[1], cfg.q_dim)
    if sh is not None:
        out = sh.act(out, "btq")
    return apply_linear(params["w_o"], out, sh=sh, kind="btd")


def attention_with_cache_write(cfg, params, x, positions, sh=None):
    """Prefill: same as :func:`attention` but also returns (k, v) to cache.

    Returned k/v are pre-GQA-expansion (B, S, KV, hd), post-RoPE."""
    qkv = apply_linear(params["w_qkv"], x, params.get("b_qkv"),
                       sh=sh, kind="btq")
    q, k, v = _split_qkv(cfg, qkv)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    groups = cfg.n_heads // cfg.n_kv_heads
    ke = _repeat_kv(k, groups)
    ve = _repeat_kv(v, groups)
    out = _sdpa(cfg, q, ke, ve, x.shape[1])
    out = out.reshape(x.shape[0], x.shape[1], cfg.q_dim)
    return apply_linear(params["w_o"], out, sh=sh, kind="btd"), k, v


def _write_rows(cache, rows, layer, idx, sh):
    """``cache[layer, b, idx[b]] = rows[b]`` for every slot ``b``: one
    scatter of B rows into the stacked (L, B, S, KV, hd) cache, in place.

    GSPMD would replicate this scatter over slot-sharded caches (it cannot
    see that row ``b`` lands in slot ``b``), so where the serving layout
    splits the slots (``ShardCtx.slot_split``) it runs once per device
    under ``shard_map``, on the device's own slots. (A scatter with the
    slots as a batching dim stays local too, but XLA then relayouts the
    whole cache around it.) Bit-exact: no arithmetic on cache values."""
    def write(c, r, i, l):
        return c.at[l, jnp.arange(c.shape[1]), i].set(r.astype(c.dtype))

    dp = sh.slot_split(cache.shape[1]) if sh is not None else None
    if dp is None:
        return write(cache, rows, idx, layer)
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(
        write, mesh=sh.mesh, in_specs=(P(None, dp), P(dp), P(dp), P()),
        out_specs=P(None, dp), check_vma=False)(cache, rows, idx, layer)


def _write_chunk(cfg, cache, rows, layer, slot, offset, sh):
    """Write one slot's C chunk rows (C, KV, hd) of one layer into the
    stacked cache, in place: at rows ``offset ..`` of a linear cache
    (clamped, as ``dynamic_update_slice`` clamps), at ``(offset + j) % S``
    of a ring (with C <= S every chunk token gets a distinct row; rows the
    chunk does not address keep their previous occupant). Both stay on the
    device that holds the slot where the data axes shard the slots; the
    cache is pinned to its placement on both sides of the write, or GSPMD
    may lay the carried cache out another way and gather it every layer."""
    if sh is not None:
        cache = sh.act(cache, "cache_kv")
    rows = rows.astype(cache.dtype)
    if cfg.sliding_window:
        ring = (offset + jnp.arange(rows.shape[0], dtype=jnp.int32)
                ) % cache.shape[2]
        cache = cache.at[layer, slot, ring].set(rows)
    else:
        cache = jax.lax.dynamic_update_slice(cache, rows[None, None],
                                             (layer, slot, offset, 0, 0))
    return sh.act(cache, "cache_kv") if sh is not None else cache


def decode_attention(cfg, params, x, k_cache, v_cache, layer, pos, sh=None):
    """One-token decode. x: (B, 1, D); caches: the stacked (L, B, S_cache,
    KV, hd) buffers, ``layer`` this layer's traced index into them; pos:
    (B,) int32 current write position (tokens seen so far).

    For sliding-window archs the cache length is the window and writes wrap
    (ring buffer); masking is by *token age*, which is wrap-invariant.
    Returns (out, k_cache, v_cache) with the layer's new rows written."""
    b, _, _ = x.shape
    s_cache = k_cache.shape[2]
    with jax.named_scope("attention"):
        # "qkv": under a decode ShardCtx this is the block's ONE gather — the
        # col-parallel qkv matmul's output replicates here, so the split /
        # RoPE / cache write / softmax / PV einsum below are all device-local
        qkv = apply_linear(params["w_qkv"], x, params.get("b_qkv"),
                           sh=sh, kind="qkv")
        q, k, v = _split_qkv(cfg, qkv)
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)

    write_idx = pos % s_cache if cfg.sliding_window else jnp.minimum(pos, s_cache - 1)
    with jax.named_scope("kv_update"):
        k_cache = _write_rows(k_cache, k[:, 0], layer, write_idx, sh)
        v_cache = _write_rows(v_cache, v[:, 0], layer, write_idx, sh)

    with jax.named_scope("attention"):
        k_l, v_l = k_cache[layer], v_cache[layer]        # (B, S, KV, hd)
        # Grouped attention WITHOUT materializing the GQA-expanded cache
        # (a repeat would cost groups x the cache bytes — §Perf iteration 2):
        # q: (B, KV, G, hd) against cache (B, S, KV, hd).
        groups = cfg.n_heads // cfg.n_kv_heads
        qg = q[:, 0].reshape(b, cfg.n_kv_heads, groups, cfg.head_dim)
        scale = cfg.head_dim ** -0.5
        logits = jnp.einsum("bngd,bsnd->bngs", qg,
                            k_l.astype(x.dtype)).astype(jnp.float32) * scale

        slots = jnp.arange(s_cache)[None, :]                       # (1, S)
        if cfg.sliding_window:
            # slot holds token (pos - age); valid if age < min(window, pos+1)
            age = (write_idx[:, None] - slots) % s_cache
            valid = age < jnp.minimum(jnp.int32(cfg.sliding_window), pos[:, None] + 1)
        else:
            valid = slots <= pos[:, None]
        logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        out = jnp.einsum("bngs,bsnd->bngd", probs, v_l.astype(x.dtype))
        out = out.reshape(b, 1, cfg.q_dim)
        out = apply_linear(params["w_o"], out, sh=sh, kind="btd")
    return out, k_cache, v_cache


def chunk_attention(cfg, params, x, k_cache, v_cache, layer, slot, offset,
                    sh=None):
    """Chunked-prefill attention: C prompt tokens of ONE slot against the
    slot-addressed cache. x: (1, C, D); caches: the stacked (L, n_slots,
    S_cache, KV, hd) buffers, ``layer`` this layer's index into them;
    layer / slot / offset are traced int32 scalars, ``offset`` = tokens
    already prefilled into the slot.

    The chunk's queries attend over [pre-write cache rows ++ the chunk's
    own K/V] with one softmax, so a partially-prefilled slot sees exactly
    the tokens a whole-prompt prefill would: cache lanes are masked to the
    real pre-offset tokens (by token age for ring caches), chunk lanes are
    causal within the chunk (+ window). The chunk's K/V are written to the
    slot's ring/linear rows only AFTER attention — writing first would
    evict ring tokens still inside earlier in-chunk queries' windows. Ring
    caches therefore require C <= S_cache (the engine clamps the chunk
    size). Returns (out, k_cache, v_cache)."""
    _, c, _ = x.shape
    s_cache = k_cache.shape[2]
    with jax.named_scope("attention"):
        qkv = apply_linear(params["w_qkv"], x, params.get("b_qkv"),
                           sh=sh, kind="qkv")
        q, k, v = _split_qkv(cfg, qkv)                       # (1, C, H/KV, hd)
        positions = offset + jnp.arange(c, dtype=jnp.int32)  # absolute positions
        q = apply_rope(q, positions[None, :], cfg.rope_theta)
        k = apply_rope(k, positions[None, :], cfg.rope_theta)

        # the slot's pre-write cache rows (1, S_cache, KV, hd)
        k_ctx = jax.lax.dynamic_slice_in_dim(k_cache[layer], slot, 1)
        v_ctx = jax.lax.dynamic_slice_in_dim(v_cache[layer], slot, 1)

        # Grouped attention without GQA-expanding the cache (same trick as
        # decode_attention): q -> (1, C, KV, G, hd) against (1, S+C, KV, hd).
        groups = cfg.n_heads // cfg.n_kv_heads
        qg = q.reshape(1, c, cfg.n_kv_heads, groups, cfg.head_dim)
        scale = cfg.head_dim ** -0.5
        k_all = jnp.concatenate([k_ctx.astype(x.dtype), k.astype(x.dtype)], axis=1)
        v_all = jnp.concatenate([v_ctx.astype(x.dtype), v.astype(x.dtype)], axis=1)
        logits = jnp.einsum("bcngd,bsnd->bngcs", qg,
                            k_all).astype(jnp.float32) * scale

        qi = jnp.arange(c, dtype=jnp.int32)
        si = jnp.arange(s_cache, dtype=jnp.int32)
        p_q = offset + qi                                    # (C,)
        if cfg.sliding_window:
            # ring slot s holds token t_s = (offset-1) - ((offset-1-s) % S);
            # negative t_s means the slot was never written for this prefix
            t_s = (offset - 1) - ((offset - 1 - si) % s_cache)
            ctx_valid = ((t_s[None, :] >= 0)
                         & (p_q[:, None] - t_s[None, :] < cfg.sliding_window))
        else:
            ctx_valid = jnp.broadcast_to(si[None, :] < offset, (c, s_cache))
        chunk_valid = qi[None, :] <= qi[:, None]
        if cfg.sliding_window:
            chunk_valid &= (qi[:, None] - qi[None, :]) < cfg.sliding_window
        valid = jnp.concatenate([ctx_valid, chunk_valid], axis=1)  # (C, S+C)
        logits = jnp.where(valid[None, None, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        out = jnp.einsum("bngcs,bsnd->bcngd", probs, v_all)
        out = out.reshape(1, c, cfg.q_dim)
        out = apply_linear(params["w_o"], out, sh=sh, kind="btd")

    with jax.named_scope("kv_update"):
        k_cache = _write_chunk(cfg, k_cache, k[0], layer, slot, offset, sh)
        v_cache = _write_chunk(cfg, v_cache, v[0], layer, slot, offset, sh)
    return out, k_cache, v_cache


def cache_length(cfg, seq_len: int) -> int:
    """Static KV-cache length for an arch at a given context length."""
    if cfg.sliding_window:
        return min(cfg.sliding_window, seq_len)
    return seq_len
