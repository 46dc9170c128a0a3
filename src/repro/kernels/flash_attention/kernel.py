"""FlashAttention-2-style Pallas TPU kernel: online-softmax blocked attention
with causal masking, padded-KV column masking, and GQA head mapping.

Grid (batch*q_heads, q_blocks, kv_blocks), kv innermost; VMEM scratch carries
(m, l, acc) across kv steps of one q block (TPU grids are sequential per
core).  Block sizes must be multiples of the (16, 128) bf16 tile — the same
alignment rule the paper derives for GPU tensor cores, with TPU constants
(DESIGN.md §2).  Fully-masked kv blocks above the causal diagonal, or fully
beyond `kv_len`, are skipped via pl.when (saving ~2x on causal prefill).

`kv_len` is the number of *real* keys: ops.py zero-pads KV up to the block
grid and the kernel masks the padded columns with NEG_INF, so non-causal and
cross-attention shapes are exact (they no longer rely on the causal rule to
hide the padding).

The forward optionally emits per-row logsumexp residuals (`return_residuals`)
for the fused backward pass in `backward.py` — together they make the kernel
a drop-in differentiable op (wired via jax.custom_vjp in ops.py).

This kernel is the §VI-C3 recommendation realized on TPU: it converts the
naive score/AOV BMM pair (whose s^2 HBM traffic makes long-sequence training
memory-bound — see EXPERIMENTS.md §Roofline baseline) into a compute-bound
streaming kernel; the h-dependence collapses onto the roofline (paper Fig.12).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def mask_block(s, qi, ki, *, block_q: int, block_kv: int, causal: bool,
               kv_len: int | None):
    """Apply causal and padded-column masking to one (block_q, block_kv)
    score tile at grid position (qi, ki).  Shared by forward and backward."""
    kv_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_kv), 1)
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                        (block_q, block_kv), 0)
        s = jnp.where(kv_pos <= q_pos, s, NEG_INF)
    if kv_len is not None:
        s = jnp.where(kv_pos < kv_len, s, NEG_INF)
    return s


def block_live(qi, ki, *, block_q: int, block_kv: int, causal: bool,
               kv_len: int | None):
    """Whether the (qi, ki) tile has any unmasked entry (skippable otherwise).
    Returns None when no masking applies (the tile always runs)."""
    live = None
    if causal:
        live = ki * block_kv <= (qi + 1) * block_q - 1
    if kv_len is not None:
        beyond = ki * block_kv < kv_len
        live = beyond if live is None else jnp.logical_and(live, beyond)
    return live


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, kv_steps: int,
                  block_q: int, block_kv: int, causal: bool, scale: float,
                  kv_len: int | None, emit_lse: bool):
    if emit_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        lse_ref, (m_ref, l_ref, acc_ref) = None, rest
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _step():
        q = q_ref[0].astype(jnp.float32)           # (bq, d)
        k = k_ref[0].astype(jnp.float32)           # (bkv, d)
        v = v_ref[0].astype(jnp.float32)           # (bkv, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = mask_block(s, qi, ki, block_q=block_q, block_kv=block_kv,
                       causal=causal, kv_len=kv_len)
        m_prev = m_ref[...]                        # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # skip tiles entirely above the causal diagonal or beyond the live keys
    live = block_live(qi, ki, block_q=block_q, block_kv=block_kv,
                      causal=causal, kv_len=kv_len)
    _step() if live is None else pl.when(live)(_step)

    @pl.when(ki == kv_steps - 1)
    def _done():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0, ...] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        if emit_lse:
            # lse = m + log(l) is the softmax log-normalizer the backward
            # recomputes p against (p = exp(s - lse)).  Fully-masked rows get
            # lse = 0: finite, and exp(NEG_INF - 0) == 0 keeps their dq/dk/dv
            # contributions exactly zero instead of NaN (m is NEG_INF there).
            lse = m_ref[...] + jnp.log(l_safe)
            lse_ref[0, ...] = jnp.where(l == 0.0, 0.0, lse)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, block_q: int = 128,
                           block_kv: int = 128, scale: float | None = None,
                           kv_len: int | None = None,
                           return_residuals: bool = False,
                           interpret: bool = False):
    """q: (bh, sq, d); k, v: (bkv_h, skv, d) with bh % bkv_h == 0 (GQA).

    Requires sq % block_q == 0 and skv % block_kv == 0 (ops.py pads).
    kv_len masks key columns >= kv_len (the zero-padded tail) with NEG_INF.
    return_residuals=True additionally returns the per-row logsumexp
    (bh, sq, 1) f32 — the saved residual for the Pallas backward pass.  The
    trailing unit dim keeps its (block_q, 1) block legal on the TPU, whose
    compiler refuses a (1, block_q) block over a 2-D (bh, sq) array.
    """
    bh, sq, d = q.shape
    bkv, skv, dk = k.shape
    assert d == dk and bh % bkv == 0
    g = bh // bkv
    assert sq % block_q == 0 and skv % block_kv == 0
    if kv_len is not None and kv_len >= skv:
        kv_len = None  # nothing padded: skip the column mask entirely
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    kv_steps = skv // block_kv
    grid = (bh, sq // block_q, kv_steps)
    from jax.experimental.pallas import tpu as pltpu
    out_shape = [jax.ShapeDtypeStruct((bh, sq, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))]
    if return_residuals:
        out_shape.append(jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32))
        out_specs.append(pl.BlockSpec((1, block_q, 1),
                                      lambda b, i, j: (b, i, 0)))
    res = pl.pallas_call(
        functools.partial(_flash_kernel, kv_steps=kv_steps, block_q=block_q,
                          block_kv=block_kv, causal=causal, scale=scale,
                          kv_len=kv_len, emit_lse=return_residuals),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j, g=g: (b // g, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j, g=g: (b // g, j, 0)),
        ],
        out_specs=out_specs if return_residuals else out_specs[0],
        out_shape=out_shape if return_residuals else out_shape[0],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return res
