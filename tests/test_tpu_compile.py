"""Compile the main-path Pallas kernels for a described TPU v5e, at
internlm2-1.8b widths (16 query heads, 8 KV heads, head_dim 128, d_model
2048, d_ff 8192, bf16).

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
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ops import (flash_attention, paged_decode,
                                               paged_decode_blocktable)
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


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_decode(shape, kv_dtype):
    rows, slots, s_max = 8, 8, 2048
    pool = shape((slots, s_max, KV_HEADS, HEAD_DIM), jnp.dtype(kv_dtype))
    args = [shape((rows, HEADS, HEAD_DIM)), pool, pool,
            shape((rows,), jnp.int32), shape((rows,), jnp.int32)]
    if kv_dtype == "int8":
        args += [shape((slots, s_max, KV_HEADS), jnp.float32)] * 2

    def fn(q, k, v, slot, lens, ks=None, vs=None):
        return paged_decode(q, k, v, slot, lens, k_scale=ks, v_scale=vs,
                            block_kv=128, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_decode_blocktable(shape, kv_dtype):
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
                                       v_scale=vs, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


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
