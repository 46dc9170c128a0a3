"""Compile the main-path Pallas kernels for a described TPU v5e, at
internlm2-1.8b widths (16 query heads, 8 KV heads, head_dim 128, d_model
2048, d_ff 8192, bf16), and the deepseek-v3-671b.ep32 serving cell's two
kernels at its shapes (the paged MLA decode kernel over a 7-layer latent
pool of 96 rows x 4096 tokens; the grouped expert product of 8 held
experts, 7168 x 2048, at decode and prefill rows), and the serving engine's
decode program at internlm2-1.8b widths, which must read its k/v block pool
in place.

No chip is attached: the TPU compiler runs against a `v5e:2x2` topology
description and raises whatever the chip's compiler would raise (illegal
block shapes, VMEM overflow).  Each test asserts that the kernel reached the
compiled program as a `tpu_custom_call`.  The kernels are asked for
`interpret=False` explicitly, because the wrappers' default follows
`jax.default_backend()`, which is the CPU here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ops import (flash_attention,
                                               paged_decode_blocktable,
                                               paged_mla_decode)
from repro.kernels.grouped_matmul.ops import grouped_matmul
from repro.kernels.fused_mlp.ops import fused_mlp_hidden
from repro.kernels.matmul.ops import matmul
from repro.kernels.quantized.ops import int8_matmul

# internlm2-1.8b
HEADS, KV_HEADS, HEAD_DIM = 16, 8, 128
D_MODEL, D_FF = 2048, 8192
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape(topo):
    chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype=BF16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)
    return make


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# block_kv None is the default chunk of 256 tokens (16 pages); 64 is a
# chunk of 4 pages
@pytest.mark.parametrize("kv_dtype,block_kv", [
    pytest.param("bfloat16", None, id="bfloat16"),
    pytest.param("int8", None, id="int8"),
    pytest.param("bfloat16", 64, id="bfloat16-chunk64"),
    pytest.param("int8", 64, id="int8-chunk64")])
def test_paged_decode_blocktable(shape, kv_dtype, block_kv):
    # the serving engine's bf16 block size for a 2048-deep pool is 16
    rows, block_size, max_blocks = 8, 16, 128
    blocks = shape((rows * max_blocks + 1, block_size, KV_HEADS, HEAD_DIM),
                   jnp.dtype(kv_dtype))
    args = [shape((rows, HEADS, HEAD_DIM)), blocks, blocks,
            shape((rows, max_blocks), jnp.int32), shape((rows,), jnp.int32)]
    if kv_dtype == "int8":
        args += [shape(blocks.shape[:3], jnp.float32)] * 2

    def fn(q, k, v, tables, lens, ks=None, vs=None):
        return paged_decode_blocktable(q, k, v, tables, lens, k_scale=ks,
                                       v_scale=vs, block_kv=block_kv,
                                       interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


def _pool_instructions(text: str, dims) -> list:
    """(name, opcode) of each compiled instruction whose result is a block
    pool of `dims` (num_blocks, block_size, nkv, hd): a stack of them, one
    layer's, or either with a page as (block_size * nkv, hd) rows."""
    nb, bs, nkv, hd = dims
    tails = (f"{nb},{bs},{nkv},{hd}]", f"{nb},{bs * nkv},{hd}]")
    found = []
    for m in re.finditer(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* "
                         r"([\w-]+)\(", text, flags=re.M):
        if (m.group(2) + "]").endswith(tails):
            found.append((m.group(1), m.group(3)))
    return found


def test_engine_decode_reads_pool_in_place(shape, monkeypatch):
    """The engine's decode program at internlm2-1.8b widths (2 of 24
    layers; 32 rows, 1,664 + 1 blocks of 16 as the chat cell runs) carries
    the k/v block pool whole through the layer scan: no copy or slice of
    the pool or of a layer of it, temporaries below one layer's K pool, and
    the block-table kernel reads each layer in place."""
    from repro.kernels.flash_attention import ops as fops
    from repro.configs.registry import get_config
    from repro.models import init_caches, init_lm
    from repro.models.linear import cast_linear_params
    from repro.serving.engine.engine import MOE_COUNTERS, _make_decode_bt
    monkeypatch.setattr(fops, "interpret_mode", lambda interpret=None: False)
    rows, blocks, block_size, layers = 32, 1664 + 1, 16, 2
    cfg = dataclasses.replace(get_config("internlm2-1.8b"), num_layers=layers,
                              dtype="bfloat16", attn_impl="paged")
    params = jax.eval_shape(lambda: cast_linear_params(
        init_lm(jax.random.PRNGKey(0), cfg), BF16, tied=cfg.tie_embeddings))
    caches = jax.eval_shape(lambda: init_caches(cfg, blocks, block_size, BF16))
    as_chip = lambda x: shape(x.shape, x.dtype)
    compiled = _make_decode_bt(cfg).lower(
        jax.tree.map(as_chip, params), shape((rows, 1), jnp.int32),
        jax.tree.map(as_chip, caches), shape((rows,), jnp.int32),
        shape((rows, 4608 // block_size), jnp.int32),
        shape((len(MOE_COUNTERS),), jnp.int32)).compile()
    text = compiled.as_text()
    moved = [(name, op) for name, op in _pool_instructions(
        text, (blocks, block_size, KV_HEADS, HEAD_DIM))
        if op in ("copy", "dynamic-slice", "dynamic-update-slice")
        or re.search(r"copy|dynamic-slice|dynamic-update-slice", name)]
    assert not moved, moved
    layer_k_bytes = blocks * block_size * KV_HEADS * HEAD_DIM * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_k_bytes
    assert "tpu_custom_call" in text and "_paged_bt_jit" in text


def test_flash_forward_with_residuals(shape):
    bh, s = 2 * HEADS, 2048
    q = shape((bh, s, HEAD_DIM))
    kv = shape((bh // (HEADS // KV_HEADS), s, HEAD_DIM))

    def fn(q, k, v):
        return flash_attention_pallas(q, k, v, causal=True,
                                      return_residuals=True, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, q, kv, kv)


def test_flash_backward(shape):
    q = shape((2, 2048, HEADS, HEAD_DIM))
    kv = shape((2, 2048, KV_HEADS, HEAD_DIM))

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(jnp.float32).sum()
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    # forward with residuals + the dq and dkv kernels
    assert text.count("tpu_custom_call") >= 3


def test_fused_mlp_forward(shape):
    x, w = shape((2048, D_MODEL)), shape((D_MODEL, D_FF))

    def fn(x, wg, wu):
        return fused_mlp_hidden(x, wg, wu, mlp_type="swiglu", interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, x, w, w)


def test_fused_mlp_backward(shape):
    x, w = shape((2048, D_MODEL)), shape((D_MODEL, D_FF))

    def loss(x, wg, wu):
        return fused_mlp_hidden(x, wg, wu, mlp_type="swiglu",
                                interpret=False).astype(jnp.float32).sum()
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), x, w, w)
    assert text.count("tpu_custom_call") >= 2


def test_matmul(shape):
    def fn(a, b):
        return matmul(a, b, interpret=False)
    assert "tpu_custom_call" in _compiled_text(
        fn, shape((2048, D_MODEL)), shape((D_MODEL, HEADS * HEAD_DIM)))


def test_int8_matmul(shape):
    def fn(a, w):
        return int8_matmul(a, w, interpret=False)
    assert "tpu_custom_call" in _compiled_text(
        fn, shape((2048, D_MODEL)), shape((D_MODEL, D_FF)))


# deepseek-v3-671b.ep32
V3_HEADS, V3_LATENT, V3_KV_LORA = 128, 640, 512
V3_D, V3_EXPERT_FF, V3_HELD = 7168, 2048, 8


def test_paged_mla_decode(shape):
    from repro.models.layers import LayerOf
    rows, block_size, max_blocks = 96, 16, 256
    pool = shape((7, rows * max_blocks + 1, block_size, V3_LATENT))

    def fn(q, pool, tables, lens, layer):
        return paged_mla_decode(q, LayerOf(pool, layer), tables, lens,
                                v_dim=V3_KV_LORA, scale=0.135,
                                interpret=False)
    text = _compiled_text(fn, shape((rows, V3_HEADS, V3_LATENT)), pool,
                          shape((rows, max_blocks), jnp.int32),
                          shape((rows,), jnp.int32), shape((), jnp.int32))
    assert "tpu_custom_call" in text and "_paged_mla_jit" in text


@pytest.mark.parametrize("rows", [96 * 8, 2048 * 8])
def test_moe_grouped_matmul(shape, rows):
    from repro.models.layers import LayerOf
    up = shape((4, V3_HELD, V3_D, V3_EXPERT_FF))
    down = shape((4, V3_HELD, V3_EXPERT_FF, V3_D))

    def fn(x, wu, wd, sizes, layer):
        h = grouped_matmul(x, LayerOf(wu, layer), sizes, interpret=False)
        return grouped_matmul(h, LayerOf(wd, layer), sizes, interpret=False)
    text = _compiled_text(fn, shape((rows, V3_D)), up, down,
                          shape((V3_HELD + 1,), jnp.int32),
                          shape((), jnp.int32))
    assert text.count("tpu_custom_call") >= 2 and "_moe_gmm_jit" in text
