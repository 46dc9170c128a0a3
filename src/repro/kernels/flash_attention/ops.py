"""jit'd public wrapper for the flash-attention kernel.

`flash_attention` takes model-layout tensors (b, s, heads, head_dim), folds
batch x heads, pads seq to the block grid, dispatches to the Pallas kernel
(TPU) or the jnp oracle (CPU fallback / use_pallas=False).

The Pallas path is *differentiable*: a jax.custom_vjp pairs the forward
kernel (which saves per-row logsumexp residuals) with the fused Pallas
backward in `backward.py`, so `attn_impl="flash"` trains end-to-end on the
measured kernels.  Padded KV columns are masked inside the kernel via a real
`kv_len` (not the causal rule), so non-causal and cross-attention shapes
with unaligned skv are exact.

With `tuned=True` the wrapper consults the autotuning cache
(`repro.tuning.cache`) for a measured-best (block_q, block_kv) for this
exact problem — and separately for the backward blocks (op
"flash_attention_bwd_*") — before falling back to the 128x128 defaults; see
`repro.tuning.search.autotune_flash_attention` / `autotune_flash_backward`.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ... import obs
from ...core.hardware import get_hardware
from ...core.quantization import round_up
from ...quant import dequantize_kv
from ...tuning.cache import lookup as _tuning_lookup
from ...tuning.cache import mixed_dtype
from ..backend import interpret_mode
from .backward import flash_attention_bwd_pallas
from .kernel import flash_attention_pallas
from .paged import (CHUNK_TOKENS, paged_decode_blocktable_pallas,
                    paged_mla_decode_pallas)
from .ref import (attention_ref, paged_decode_blocktable_ref,
                  paged_mla_decode_ref)


def _fold(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unfold(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


class _FlashConfig(NamedTuple):
    """Static kernel config threaded through the custom_vjp (hashable)."""
    causal: bool
    block_q: int
    block_kv: int
    bwd_block_q: int
    bwd_block_kv: int
    interpret: bool


def _pad_seq(x, target: int):
    s = x.shape[1]
    return x if s == target else jnp.pad(x, ((0, 0), (0, target - s), (0, 0)))


def _flash_fwd(cfg: _FlashConfig, q, k, v, need_residuals: bool):
    """Pad folded (bh, s, d) tensors to the block grid and run the forward
    kernel.  Returns (out, lse) sliced back to the real sq; lse is None on
    the residual-free path (inference forwards skip the logsumexp work —
    pallas_call is opaque to XLA, so DCE could never drop it)."""
    sq, skv = q.shape[1], k.shape[1]
    qf = _pad_seq(q, round_up(sq, cfg.block_q))
    kf = _pad_seq(k, round_up(skv, cfg.block_kv))
    vf = _pad_seq(v, round_up(skv, cfg.block_kv))
    res = flash_attention_pallas(
        qf, kf, vf, causal=cfg.causal, block_q=cfg.block_q,
        block_kv=cfg.block_kv, kv_len=skv, return_residuals=need_residuals,
        interpret=cfg.interpret)
    if need_residuals:
        out, lse = res
        return out[:, :sq], lse[:, :sq]
    return res[:, :sq], None


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_core(cfg: _FlashConfig, q, k, v):
    return _flash_fwd(cfg, q, k, v, need_residuals=False)[0]


def _flash_core_fwd(cfg: _FlashConfig, q, k, v):
    out, lse = _flash_fwd(cfg, q, k, v, need_residuals=True)
    return out, (q, k, v, out, lse)


def _flash_core_bwd(cfg: _FlashConfig, residuals, g):
    q, k, v, out, lse = residuals
    sq, skv = q.shape[1], k.shape[1]
    bq, bkv = cfg.bwd_block_q, cfg.bwd_block_kv
    sq_p, skv_p = round_up(sq, bq), round_up(skv, bkv)
    # padded query rows carry do = 0 (and lse = 0, kept finite by the
    # forward's masked-row guard), so they contribute exactly zero gradient
    dq, dk_h, dv_h = flash_attention_bwd_pallas(
        _pad_seq(q, sq_p), _pad_seq(k, skv_p), _pad_seq(v, skv_p),
        _pad_seq(out, sq_p), _pad_seq(lse, sq_p),
        _pad_seq(g, sq_p), causal=cfg.causal, block_q=bq, block_kv=bkv,
        kv_len=skv, interpret=cfg.interpret)
    bh = q.shape[0]
    bkv_h = k.shape[0]
    grp = bh // bkv_h
    # dk/dv come back at query-head resolution: reduce each GQA head group
    dk = dk_h[:, :skv].reshape(bkv_h, grp, skv, -1).sum(1)
    dv = dv_h[:, :skv].reshape(bkv_h, grp, skv, -1).sum(1)
    return (dq[:, :sq].astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_kv",
                                             "bwd_block_q", "bwd_block_kv",
                                             "interpret", "use_pallas"))
def _flash_jit(q, k, v, *, causal: bool, block_q: int, block_kv: int,
               bwd_block_q: int, bwd_block_kv: int, interpret: bool,
               use_pallas: bool):
    b, sq, a, d = q.shape
    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    if not use_pallas:
        return _unfold(attention_ref(qf, kf, vf, causal=causal), b, a)
    cfg = _FlashConfig(causal=causal, block_q=block_q, block_kv=block_kv,
                       bwd_block_q=bwd_block_q, bwd_block_kv=bwd_block_kv,
                       interpret=interpret)
    return _unfold(_flash_core(cfg, qf, kf, vf), b, a)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_kv: int = 128, bwd_block_q: int = 128,
                    bwd_block_kv: int = 128,
                    interpret: Optional[bool] = None,
                    use_pallas: bool = True, tuned: bool = False,
                    hw_name: Optional[str] = None):
    """q: (b, sq, a, d); k, v: (b, skv, kv_heads, d).  Returns (b, sq, a, d).

    Differentiable: the Pallas path carries a custom VJP onto the fused
    backward kernels (backward.py), so this op can sit inside value_and_grad
    / train_step.  (bwd_block_q, bwd_block_kv) block the backward grids
    independently of the forward.

    tuned=True overrides the forward (block_q, block_kv) — and the backward
    blocks, from the separate "flash_attention_bwd_*" entries — with the
    autotuning cache's measured-best config for this problem when one exists
    (cache misses keep the defaults).  Lookups run at trace time, outside
    the jit.
    """
    tuned_hit = None
    if tuned and use_pallas:
        b, sq, a, d = q.shape
        skv = k.shape[1]
        dtype = jnp.dtype(q.dtype).name
        hw = hw_name or get_hardware().name
        op = ("flash_attention_causal" if causal else "flash_attention_full")
        cfg = _tuning_lookup(op, (b, sq, skv, a, d), dtype, hw)
        tuned_hit = cfg is not None
        if cfg is not None:
            block_q = cfg.blocks["block_q"]
            block_kv = cfg.blocks["block_kv"]
        op_bwd = ("flash_attention_bwd_causal" if causal
                  else "flash_attention_bwd_full")
        cfg_bwd = _tuning_lookup(op_bwd, (b, sq, skv, a, d), dtype, hw)
        if cfg_bwd is not None:
            bwd_block_q = cfg_bwd.blocks["block_q"]
            bwd_block_kv = cfg_bwd.blocks["block_kv"]
    if obs.enabled():
        obs.record_dispatch(
            "flash_attention_causal" if causal else "flash_attention_full",
            impl="pallas" if use_pallas else "jnp", shape=q.shape,
            blocks={"block_q": block_q,
                    "block_kv": block_kv} if use_pallas else None,
            tuned_hit=tuned_hit)
    return _flash_jit(q, k, v, causal=causal, block_q=block_q,
                      block_kv=block_kv, bwd_block_q=bwd_block_q,
                      bwd_block_kv=bwd_block_kv,
                      interpret=interpret_mode(interpret),
                      use_pallas=use_pallas)


# --- paged decode (serving engine) ---------------------------------------------------

def _stack_of(x):
    """(stack, layer) of a `models.layers.LayerOf`, or of a plain pool as a
    stack of one layer."""
    from ...models.layers import LayerOf
    return (x.stack, x.index) if isinstance(x, LayerOf) else (x[None], 0)


@functools.partial(jax.jit, static_argnames=("block_kv", "interpret",
                                             "use_pallas"))
def _paged_bt_jit(q, k_blocks, v_blocks, block_tables, lengths, layer,
                  k_scale, v_scale, *, block_kv: Optional[int],
                  interpret: bool, use_pallas: bool):
    if k_scale is not None:
        # the scales' device layout keeps the block axis minor-most, so a
        # layer's scale pages are relaid out as rows for the kernel anyway
        k_scale, v_scale = k_scale[layer], v_scale[layer]
    if not use_pallas:
        k_blocks, v_blocks = k_blocks[layer], v_blocks[layer]
        if k_scale is not None:
            k_blocks = dequantize_kv(k_blocks, k_scale, q.dtype)
            v_blocks = dequantize_kv(v_blocks, v_scale, q.dtype)
        return paged_decode_blocktable_ref(q, k_blocks.astype(q.dtype),
                                           v_blocks.astype(q.dtype),
                                           block_tables, lengths)
    return paged_decode_blocktable_pallas(q, k_blocks, v_blocks,
                                          block_tables, lengths, layer,
                                          k_scale=k_scale, v_scale=v_scale,
                                          block_kv=block_kv,
                                          interpret=interpret)


def paged_decode_blocktable(q, k_blocks, v_blocks, block_tables, lengths, *,
                            k_scale=None, v_scale=None,
                            block_kv: Optional[int] = None,
                            interpret: Optional[bool] = None,
                            use_pallas: bool = True,
                            tuned: bool = False,
                            hw_name: Optional[str] = None):
    """Block-table decode attention over a physical KV block pool.

    q: (b, a, d) — one query token per active request row; k_blocks,
    v_blocks: (num_blocks, block_size, nkv, d), or `models.layers.LayerOf`
    a stack of every layer's pool (read in place, no layer copied out);
    block_tables: (b, max_blocks) row -> physical block ids; lengths: (b,)
    live kv entries (0 = dead row -> zero output).  Returns (b, a, d).

    k_scale/v_scale: (num_blocks, block_size, nkv) f32 per-(token, kv_head)
    scales for an int8 block pool, `LayerOf` their stacks beside a stacked
    pool; dequantized per chunk in-kernel on the Pallas path, up front on
    the jnp path.

    block_kv is the Pallas kernel's chunk length in tokens, rounded up to
    whole blocks (None: `paged.CHUNK_TOKENS`); each row reads only its live
    blocks, a chunk of them per step of the kernel's loop.

    tuned=True overrides block_kv with the autotuning cache's measured-best
    for this block-pool shape (op "paged_decode_blocktable") when one exists
    — see `tuning.search.autotune_paged_decode_blocktable`, which sweeps the
    physical block size jointly and also records the winning pool geometry
    under op "paged_decode_blocktable_pool" for the engine to consult.
    Quantized pools key the lookup by the mixed dtype pair.
    """
    (k_blocks, layer), (v_blocks, _) = _stack_of(k_blocks), _stack_of(v_blocks)
    if k_scale is not None:
        k_scale, v_scale = _stack_of(k_scale)[0], _stack_of(v_scale)[0]
    b, a, d = q.shape
    _, nb, block_size, nkv, _ = k_blocks.shape
    tuned_hit = None
    dtype = jnp.dtype(q.dtype).name
    if k_scale is not None:
        dtype = mixed_dtype(dtype, jnp.dtype(k_blocks.dtype).name)
    if tuned and use_pallas:
        cfg = _tuning_lookup("paged_decode_blocktable",
                             (b, nb, block_size, nkv, a, d),
                             dtype, hw_name or get_hardware().name)
        tuned_hit = cfg is not None
        if cfg is not None:
            block_kv = cfg.blocks["block_kv"]
    if obs.enabled():
        obs.record_dispatch(
            "paged_decode_blocktable",
            impl="pallas" if use_pallas else "jnp", shape=q.shape,
            blocks={"block_kv": block_kv or CHUNK_TOKENS,
                    "block_size": block_size} if use_pallas else None,
            tuned_hit=tuned_hit)
    return _paged_bt_jit(q, k_blocks, v_blocks, block_tables, lengths,
                         jnp.asarray(layer, jnp.int32), k_scale, v_scale,
                         block_kv=block_kv,
                         interpret=interpret_mode(interpret),
                         use_pallas=use_pallas)


@functools.partial(jax.jit, static_argnames=("v_dim", "scale", "block_kv",
                                             "interpret", "use_pallas"))
def _paged_mla_jit(q, latent_blocks, block_tables, lengths, layer, *,
                   v_dim: int, scale: float, block_kv: Optional[int],
                   interpret: bool, use_pallas: bool):
    if not use_pallas:
        return paged_mla_decode_ref(q, latent_blocks[layer].astype(q.dtype),
                                    block_tables, lengths, v_dim=v_dim,
                                    scale=scale)
    return paged_mla_decode_pallas(q, latent_blocks.astype(q.dtype),
                                   block_tables, lengths, layer, v_dim=v_dim,
                                   scale=scale, block_kv=block_kv,
                                   interpret=interpret)


def paged_mla_decode(q, latent_blocks, block_tables, lengths, *, v_dim: int,
                     scale: float, block_kv: Optional[int] = None,
                     interpret: Optional[bool] = None,
                     use_pallas: bool = True):
    """Absorbed MLA decode attention over a latent block pool.

    q: (b, a, c) one latent-space query per head and row (c = kv_lora +
    rope, padded to whole lanes); latent_blocks: (num_blocks, block_size,
    c), or `models.layers.LayerOf` a stack of layers' pools (read in
    place); block_tables: (b, max_blocks); lengths: (b,) live entries (0 =
    dead row -> zeros).  Returns (b, a, v_dim), the heads' outputs in the
    latent space.  The Pallas kernel (`paged.paged_mla_decode_pallas`)
    reads each row's live pages only; block_kv is its chunk in tokens,
    rounded up to whole pages.
    """
    stack, layer = _stack_of(latent_blocks)
    if obs.enabled():
        obs.record_dispatch(
            "paged_mla_decode", impl="pallas" if use_pallas else "jnp",
            shape=q.shape,
            blocks={"block_kv": block_kv or CHUNK_TOKENS,
                    "block_size": stack.shape[2]} if use_pallas else None)
    return _paged_mla_jit(q, stack, block_tables, lengths,
                          jnp.asarray(layer, jnp.int32), v_dim=v_dim,
                          scale=float(scale), block_kv=block_kv,
                          interpret=interpret_mode(interpret),
                          use_pallas=use_pallas)
