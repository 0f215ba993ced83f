"""The main path's Pallas kernels compile for a TPU v5e, at published widths.

Each case compiles one kernel for a described (not attached) v5e chip and
checks that the kernel survives as a ``tpu_custom_call``: what the chip's
compiler refuses — a block that breaks the TPU tiling rule, an op the vector
unit lacks, more VMEM than a kernel may use — fails here, with no chip.
Nothing runs, so nothing about results or time is checked (the kernel
parity tests and ``chip_smoke.py`` do that). The serving programs that
write the K/V cache are compiled whole, for the temporary memory the chip
compiler gives them.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library, and every test worker
imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.binary_matmul import binary_matmul_pallas
from repro.kernels.stoch_binarize import binarize_pack_pallas
from repro.xnor.conv.kernel import patch_pack_pallas
from repro.xnor.kernel import lane_words, sign_pack_rows, xnor_matmul_pallas

D_MODEL, QKV, D_FF = 3072, 3584, 12288     # starcoder2-3b projections


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compile_text(one_chip, no_persistent_cache):
    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()
    return run


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("m", [16, 256], ids=["decode", "prefill"])
@pytest.mark.parametrize("k,n", [(D_MODEL, QKV), (D_MODEL, D_MODEL),
                                 (D_MODEL, D_FF), (D_FF, D_MODEL)],
                         ids=["qkv", "o", "up", "down"])
def test_binary_matmul(compile_text, k, n, m, dtype):
    """The blocks the wrapper chooses (one M-block, wide block_n/block_k)
    and the integer expansion's bitcast, in bf16 and f32 compute."""
    text = compile_text(
        lambda x, w, s: binary_matmul_pallas(x, w, s, compute_dtype=dtype),
        ((m, k), dtype), ((k // 32, n), jnp.int32), ((n,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k", [D_MODEL, D_FF])
def test_sign_pack(compile_text, k):
    text = compile_text(lambda x: sign_pack_rows(x, block_m=8),
                        ((8, k), jnp.bfloat16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k,n", [(D_MODEL, QKV), (D_FF, D_MODEL)])
def test_xnor_matmul(compile_text, k, n):
    bk = 32 * lane_words(k // 32, 16)
    text = compile_text(
        lambda a, w, s: xnor_matmul_pallas(a, w, s, k_total=k, block_m=8,
                                           block_k=bk),
        ((8, k // 32), jnp.int32), ((k // 32, n), jnp.int32),
        ((n,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("stochastic", [False, True], ids=["det", "stoch"])
def test_binarize_pack(compile_text, stochastic):
    w = ((D_MODEL, D_FF), jnp.bfloat16)
    if stochastic:
        text = compile_text(
            lambda w, b: binarize_pack_pallas(w, b, stochastic=True),
            w, ((D_MODEL, D_FF), jnp.uint32))
    else:
        text = compile_text(
            lambda w: binarize_pack_pallas(w, stochastic=False), w)
    assert "tpu_custom_call" in text


def test_patch_pack_vgg_widest_block(compile_text):
    """VGG-16 at width 1.0: 512 channels at 4x4, SAME 3x3, batch 8."""
    text = compile_text(
        lambda xp: patch_pack_pallas(xp, ksize=(3, 3), oh=4, ow=4),
        ((8, 6, 6, 512), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("program",
                         ["_decode", "_decode_prefill", "_prefill_chunk"])
def test_serving_program_writes_cache_in_place(one_chip, no_persistent_cache,
                                               program):
    """starcoder2-3b widths (4 of 30 layers, bf16), 32 slots x 4096
    positions, cache donated: each program that writes the cache needs
    less temporary memory than one layer's K, so no program copies or
    rewrites the cache (a whole-cache rewrite held 0.94 GB here)."""
    from repro.configs.base import ModelConfig
    from repro.models import transformer as T
    from repro.serve.engine import ServeEngine

    cfg = ModelConfig(name="starcoder2-3b", family="dense", n_layers=4,
                      d_model=D_MODEL, n_heads=24, n_kv_heads=2,
                      head_dim=128, d_ff=D_FF, vocab_size=49152,
                      mlp_type="gelu", dtype="bfloat16")
    slots, ctx, chunk = 32, 4096, 256

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = abstract(jax.eval_shape(lambda: T.init_lm(
        cfg, jax.random.key(0), dtype=jnp.bfloat16)))
    cache = abstract(jax.eval_shape(lambda: T.init_cache(cfg, slots, ctx)))
    engine = ServeEngine(cfg, params)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    logits = arg((slots, cfg.vocab_size), jnp.bfloat16)
    args = {"_decode": (params, cache, arg((slots, 1))),
            "_decode_prefill": (params, cache, logits, arg((slots, 1)),
                                arg((slots,), jnp.bool_), arg((1, chunk)),
                                arg(()), arg(())),
            "_prefill_chunk": (params, cache, logits, arg((1, chunk)),
                               arg(()), arg(()))}[program]
    compiled = getattr(engine, program).lower(*args).compile()
    layer_k_bytes = slots * ctx * cfg.n_kv_heads * cfg.head_dim * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_k_bytes
