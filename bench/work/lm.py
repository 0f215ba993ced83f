"""Operations and bytes of the decoder LM's serving programs, from logical
shapes.

The arithmetic of ``repro.engine.costs`` (1 bit per packed weight in int32
words plus an f32 scale per output channel; bf16 activations), copied so the
yardstick stays with the benchmark. Work is what the layer needs, whatever
the kernel does: live K/V rows only, packed weights and the bf16 ``lm_head``
once per step, projections as bf16 x ±1 matmuls (bf16 peak).

``model`` is the ``model`` dict of a configuration file.
"""
from __future__ import annotations

from bench.work.roofline import add, work

PACK = 32
ACT = 2          # bf16 bytes
KV = 2           # bf16 cache bytes


def projections(m: dict) -> list[tuple[int, int]]:
    """(K, N) of every packed projection of one layer."""
    d, hd = m["d_model"], m["head_dim"]
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    out = [(d, q + 2 * kv), (q, d)]
    if m.get("mlp_type", "glu") == "glu":
        out += [(d, m["d_ff"]), (d, m["d_ff"]), (m["d_ff"], d)]
    else:
        out += [(d, m["d_ff"]), (m["d_ff"], d)]
    return out


def proj_params(m: dict) -> int:
    """Packed projection weights of the whole stack."""
    return m["n_layers"] * sum(k * n for k, n in projections(m))


def packed_weight_bytes(m: dict) -> int:
    per_layer = sum(-(-k // PACK) * n * 4 + n * 4 for k, n in projections(m))
    return m["n_layers"] * per_layer


def head_bytes(m: dict) -> int:
    return m["d_model"] * m["vocab_size"] * ACT


def kv_row_bytes(m: dict) -> int:
    """K and V of one cached token over all layers."""
    return m["n_layers"] * 2 * m["n_kv_heads"] * m["head_dim"] * KV


def attn_ops(m: dict, key_rows: float) -> float:
    """QK and PV of one query against ``key_rows`` keys, all layers."""
    return m["n_layers"] * 4 * m["n_heads"] * m["head_dim"] * key_rows


def weights_once(m: dict) -> dict:
    return work(None, packed_weight_bytes(m) + head_bytes(m))


def decode_rows(m: dict, n: int, rows: int) -> dict:
    """One token for each of ``n`` live slots attending to ``rows`` cached
    rows in all (their new rows included). Weights not included."""
    ops = (2 * proj_params(m) * n + attn_ops(m, rows)
           + 2 * m["d_model"] * m["vocab_size"] * n)
    nbytes = kv_row_bytes(m) * rows + n * (kv_row_bytes(m)
                                           + 2 * m["d_model"] * ACT)
    return work({"bf16": ops}, nbytes)


def chunk_rows(m: dict, offset: int, c: int) -> dict:
    """``c`` prompt tokens of one slot after ``offset`` cached ones; logits
    of the last token only. Weights not included."""
    key_rows = c * offset + c * (c + 1) / 2
    ops = (2 * proj_params(m) * c + attn_ops(m, key_rows)
           + 2 * m["d_model"] * m["vocab_size"])
    nbytes = kv_row_bytes(m) * (offset + c) + c * m["d_model"] * ACT
    return work({"bf16": ops}, nbytes)


def step(m: dict, n: int = 0, rows: int = 0, chunk=None) -> dict:
    """One serving program call: decode of ``n`` live slots over ``rows``
    cached rows, and/or one prefill chunk ``(offset, c)``; the weights are
    read once."""
    parts = [weights_once(m)]
    if n:
        parts.append(decode_rows(m, n, rows))
    if chunk is not None:
        parts.append(chunk_rows(m, *chunk))
    return add(*parts)


def binary_matmul_call(mm: int, k: int, n: int) -> dict:
    """One ``binary_matmul`` kernel call: bf16 (M, K) x packed ±1 (K, N)
    with f32 scale and f32 output."""
    nbytes = mm * k * ACT + -(-k // PACK) * n * 4 + n * 4 + mm * n * 4
    return work({"bf16": 2.0 * mm * k * n}, nbytes)
