"""FlashAttention-2-style Pallas TPU backward kernels: dq, dk, dv.

Two kernels, mirroring the FA2 split (Dao 2023, §3.1) so neither needs
atomics on a sequential TPU grid:

  dq  — grid (batch*q_heads, q_blocks, kv_blocks), kv innermost; a VMEM
        accumulator carries dq for one q block across kv steps (the same
        iteration order as the forward).
  dkv — grid (batch*q_heads, kv_blocks, q_blocks), q innermost; VMEM
        accumulators carry (dk, dv) for one kv block across q steps.

Both recompute the score tile from (q, k) and the softmax probabilities from
the saved per-row logsumexp (`p = exp(s·scale - lse)`) instead of storing
the s^2 probability matrix — the whole point of the fused backward: HBM
traffic stays O(s·block), matching the forward's roofline position.

GQA: inputs k, v stay at kv-head resolution (the BlockSpec maps q-head b to
kv-head b // g, as in the forward); dk/dv are emitted at *query*-head
resolution (bh rows) and ops.py reduces the g-sized head groups outside the
kernel — a (g·skv·d) temp instead of cross-grid-step output revisiting,
which Pallas TPU does not order-guarantee.

Masking reuses the forward's `mask_block` (causal + padded-KV `kv_len`
columns); masked entries give p = 0 and ds = 0, so padded keys and padded
query rows (do = 0 there) contribute exactly zero gradient.  Fully-masked
rows carry lse = 0 from the forward guard, keeping every exp() finite.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .kernel import block_live, mask_block


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_ref,
               *, kv_steps: int, block_q: int, block_kv: int, causal: bool,
               scale: float, kv_len: int | None):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _step():
        q = q_ref[0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0].astype(jnp.float32)            # (bkv, d)
        v = v_ref[0].astype(jnp.float32)            # (bkv, d)
        do = do_ref[0].astype(jnp.float32)          # (bq, d)
        lse = lse_ref[0]                            # (bq, 1)
        di = di_ref[0]                              # (bq, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = mask_block(s, qi, ki, block_q=block_q, block_kv=block_kv,
                       causal=causal, kv_len=kv_len)
        p = jnp.exp(s - lse)                        # (bq, bkv)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di) * scale
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    live = block_live(qi, ki, block_q=block_q, block_kv=block_kv,
                      causal=causal, kv_len=kv_len)
    _step() if live is None else pl.when(live)(_step)

    @pl.when(ki == kv_steps - 1)
    def _done():
        dq_ref[0, ...] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, q_steps: int, block_q: int, block_kv: int,
                causal: bool, scale: float, kv_len: int | None):
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _step():
        q = q_ref[0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0].astype(jnp.float32)            # (bkv, d)
        v = v_ref[0].astype(jnp.float32)            # (bkv, d)
        do = do_ref[0].astype(jnp.float32)          # (bq, d)
        lse = lse_ref[0]                            # (bq, 1)
        di = di_ref[0]                              # (bq, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = mask_block(s, qi, ki, block_q=block_q, block_kv=block_kv,
                       causal=causal, kv_len=kv_len)
        p = jnp.exp(s - lse)                        # (bq, bkv)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    live = block_live(qi, ki, block_q=block_q, block_kv=block_kv,
                      causal=causal, kv_len=kv_len)
    _step() if live is None else pl.when(live)(_step)

    @pl.when(qi == q_steps - 1)
    def _done():
        dk_ref[0, ...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd_pallas(q, k, v, o, lse, do, *, causal: bool = True,
                               block_q: int = 128, block_kv: int = 128,
                               scale: float | None = None,
                               kv_len: int | None = None,
                               interpret: bool = False):
    """Fused backward for `flash_attention_pallas`.

    q, do: (bh, sq, d); k, v: (bkv_h, skv, d); o: (bh, sq, d);
    lse: (bh, sq, 1) f32 from the forward's return_residuals=True (the
    unit lane dim keeps the (block_q, 1) row blocks legal on the TPU).
    Requires sq % block_q == 0 and skv % block_kv == 0 (ops.py pads).

    Returns (dq, dk_heads, dv_heads) with dk/dv at query-head resolution
    (bh, skv, d) — the caller reduces head groups g = bh // bkv_h.
    """
    bh, sq, d = q.shape
    bkv, skv, _ = k.shape
    assert bh % bkv == 0
    g = bh // bkv
    assert sq % block_q == 0 and skv % block_kv == 0
    if kv_len is not None and kv_len >= skv:
        kv_len = None
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    # di = rowsum(do * o): the softmax-jacobian diagonal term, cheap in XLA
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                 keepdims=True)

    from jax.experimental.pallas import tpu as pltpu
    q_steps, kv_steps = sq // block_q, skv // block_kv

    qspec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kvspec = pl.BlockSpec((1, block_kv, d), lambda b, i, j, g=g: (b // g, j, 0))
    rowspec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, kv_steps=kv_steps, block_q=block_q,
                          block_kv=block_kv, causal=causal, scale=scale,
                          kv_len=kv_len),
        grid=(bh, q_steps, kv_steps),
        in_specs=[qspec, kvspec, kvspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, di)

    # dkv grid transposes the block walk: kv outer, q inner
    qspec_t = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
    kvspec_t = pl.BlockSpec((1, block_kv, d), lambda b, j, i, g=g: (b // g, j, 0))
    rowspec_t = pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0))
    dkvspec = pl.BlockSpec((1, block_kv, d), lambda b, j, i: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, q_steps=q_steps, block_q=block_q,
                          block_kv=block_kv, causal=causal, scale=scale,
                          kv_len=kv_len),
        grid=(bh, kv_steps, q_steps),
        in_specs=[qspec_t, kvspec_t, kvspec_t, qspec_t, rowspec_t, rowspec_t],
        out_specs=[dkvspec, dkvspec],
        out_shape=[jax.ShapeDtypeStruct((bh, skv, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, skv, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_kv, d), jnp.float32),
                        pltpu.VMEM((block_kv, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, di)
    return dq, dk, dv
