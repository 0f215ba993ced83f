"""Feed-forward blocks: SwiGLU (llama-family) and 2-matmul GELU (starcoder,
musicgen)."""
from __future__ import annotations

import jax

from repro.models.layers import apply_linear


def init_mlp(key, cfg, init_fn, d_ff=None) -> dict:
    d_ff = d_ff or cfg.d_ff
    if cfg.mlp_type == "glu":
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "w_gate": init_fn(k1, (cfg.d_model, d_ff)),
            "w_up": init_fn(k2, (cfg.d_model, d_ff)),
            "w_down": init_fn(k3, (d_ff, cfg.d_model)),
        }
    k1, k2 = jax.random.split(key)
    return {
        "wi": init_fn(k1, (cfg.d_model, d_ff)),
        "wo": init_fn(k2, (d_ff, cfg.d_model)),
    }


def mlp(cfg, params: dict, x: jax.Array, sh=None) -> jax.Array:
    # activation constraints ride through the dispatch seam (sh/kind on
    # apply_linear), so packed / xnor serving leaves get the same TP layout
    # as the dense path
    with jax.named_scope("mlp"):
        if "w_gate" in params:
            g = apply_linear(params["w_gate"], x, sh=sh, kind="btf")
            u = apply_linear(params["w_up"], x, sh=sh, kind="btf")
            h = jax.nn.silu(g) * u
            return apply_linear(params["w_down"], h, sh=sh, kind="btd")
        h = apply_linear(params["wi"], x, sh=sh, kind="btf")
        h = jax.nn.gelu(h)
        return apply_linear(params["wo"], h, sh=sh, kind="btd")
