"""Full models: decoder-only LM, VLM (patch-embed stub), encoder-decoder
(whisper, conv-frontend stub), with train/prefill/decode entry points.

Parameter pytree layout:
  embed        (v, h)            token embedding (TP: vocab-sharded)
  pos_embed    (max_pos, h)      only for pos_emb == "learned"
  seg{i}       stacked params    one entry per stack_plan segment
  shared       zamba2 shared attention block (hybrid only)
  final_norm
  lm_head      (h, v)            untied output head (TP: vocab-sharded)
  encoder      whisper encoder stack (+ cross-attn lives in decoder blocks)
  mtp          deepseek multi-token-prediction head
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import apply_attention, init_attention
from .blocks import (apply_stack, init_cache_segment, init_segment,
                     init_shared, stack_plan, _init_one, _apply_core)
from .layers import compute_dtype, dense_init, embed_init, norm_apply, norm_init
from .linear import linear, resolve_impl


def init_lm(key, cfg: ModelConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, 16)
    params: Dict[str, Any] = {
        "embed": embed_init(ks[0], cfg.padded_vocab_size, cfg.d_model)}
    if cfg.pos_emb == "learned":
        max_pos = max(cfg.encoder_seq, 8192)
        params["pos_embed"] = embed_init(ks[1], max_pos, cfg.d_model)
    for i, (kind, n) in enumerate(stack_plan(cfg)):
        params[f"seg{i}"] = init_segment(ks[2 + i], cfg, kind, n)
    sh = init_shared(ks[10], cfg)
    if sh is not None:
        params["shared"] = sh
    params["final_norm"] = norm_init(cfg.d_model, cfg.norm_type)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[11], cfg.d_model, cfg.padded_vocab_size)
    if cfg.is_encoder_decoder:
        params["encoder"] = _init_encoder(ks[12], cfg)
        params["xattn"] = jax.vmap(
            lambda k: {"norm": norm_init(cfg.d_model, cfg.norm_type),
                       "attn": init_attention(k, cfg)}
        )(jax.random.split(ks[13], cfg.num_layers))
    if cfg.mtp_depth:
        params["mtp"] = {
            "proj": dense_init(ks[14], 2 * cfg.d_model, cfg.d_model),
            "block": _init_one(ks[15], cfg, "dense"),
            "norm": norm_init(cfg.d_model, cfg.norm_type),
        }
    return params


def _init_encoder(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    return {"seg0": init_segment(ks[0], cfg, "dense", cfg.num_encoder_layers),
            "final_norm": norm_init(cfg.d_model, cfg.norm_type)}


# --- caches --------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, s_max: int, dtype=jnp.bfloat16):
    return [init_cache_segment(cfg, kind, n, batch, s_max, dtype)
            for kind, n in stack_plan(cfg)]


# --- encoder (whisper; conv frontend is a stub: frames are precomputed embeddings) ---

def apply_encoder(params, frames, cfg: ModelConfig, remat: str = "none"):
    """frames: (b, enc_seq, h) precomputed log-mel conv embeddings (stub)."""
    dt = compute_dtype(cfg.dtype)
    x = frames.astype(dt)
    if cfg.pos_emb == "learned":
        pos = jnp.arange(x.shape[1])
        x = x + params["pos_embed"][pos].astype(dt)[None]
    enc = params["encoder"]
    positions = jnp.arange(x.shape[1])

    def body(carry, p_l):
        h, _ = carry
        hn = norm_apply(p_l["norm1"], h, cfg.norm_type, cfg.norm_eps)
        a, _ = apply_attention(p_l["attn"], hn, cfg, positions=positions, causal=False)
        h = h + a
        from .mlp import apply_mlp
        h = h + apply_mlp(p_l["mlp"], norm_apply(p_l["norm2"], h, cfg.norm_type, cfg.norm_eps), cfg)
        return (h, jnp.zeros(())), None

    if remat == "full":
        body = jax.checkpoint(body, prevent_cse=False)
    (x, _), _ = jax.lax.scan(body, (x, jnp.zeros(())), enc["seg0"])
    return norm_apply(enc["final_norm"], x, cfg.norm_type, cfg.norm_eps)


# --- main forward --------------------------------------------------------------------

def apply_lm(params, tokens, cfg: ModelConfig, *,
             positions=None, caches=None, cache_index=None, decode=False,
             remat: str = "none", patch_embeds=None, encoder_frames=None,
             enc_out=None, return_hidden: bool = False, block_tables=None,
             token_mask=None, return_stats: bool = False):
    """tokens: (b, s) int32.  Returns (logits, new_caches, aux, [hidden]).

    patch_embeds: (b, n_patches, h) VLM stub — prepended to the token stream.
    encoder_frames: (b, enc_seq, h) whisper stub — runs the encoder.
    enc_out: precomputed encoder output (decode steps reuse it).
    block_tables: (b, max_blocks) — caches are a physical KV *block pool*
    (kv leaves (n, num_blocks, block_size, kv, hd)) and row b's logical
    block j lives at block_tables[b, j]; single-token decode only.
    token_mask: (b, s) rows that hold a token; the drop-free MoE path
    (cfg.moe_dispatch == "dropless") routes no other row.
    return_stats: also return the MoE stats (`moe.MOE_STATS` int32,
    zeros without drop-free MoE) after aux.
    """
    from ..parallel.sharding import constrain
    dt = compute_dtype(cfg.dtype)
    b, s = tokens.shape
    x = params["embed"][tokens].astype(dt) * jnp.sqrt(float(cfg.d_model)).astype(dt)

    if patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(dt), x], axis=1)
        s = x.shape[1]
    # anchor the activation layout: batch over DP axes, seq/dim unsharded.
    # without this single constraint the SPMD partitioner is free to
    # replicate the whole forward pass dp-fold (observed; EXPERIMENTS.md §Perf)
    x = constrain(x, "btd")

    if positions is None:
        start = jnp.asarray(0 if cache_index is None else cache_index)
        # vector cache_index (serving engine): per-row (b, s) positions
        positions = (start[:, None] + jnp.arange(s)[None] if start.ndim
                     else start + jnp.arange(s))
    if cfg.pos_emb == "learned":
        pe = params["pos_embed"][positions].astype(dt)
        x = x + (pe if positions.ndim > 1 else pe[None])

    if cfg.is_encoder_decoder and enc_out is None:
        assert encoder_frames is not None, "whisper needs encoder frames"
        enc_out = apply_encoder(params, encoder_frames, cfg, remat)

    segs = [(kind, params[f"seg{i}"]) for i, (kind, n) in enumerate(stack_plan(cfg))]
    x, new_caches, aux, stats = apply_stack(
        segs, cfg, x, positions=positions, caches=caches,
        cache_index=cache_index, decode=decode,
        shared=params.get("shared"), remat=remat, block_tables=block_tables,
        token_mask=token_mask, return_stats=True)

    # whisper cross-attention: applied as a post-pass per decoder layer would
    # interleave; for the stub we apply the stacked cross-attn blocks after the
    # self-attn stack (documented simplification — same GEMM inventory).
    if cfg.is_encoder_decoder:
        def xbody(h, p_l):
            hn = norm_apply(p_l["norm"], h, cfg.norm_type, cfg.norm_eps)
            a, _ = apply_attention(p_l["attn"], hn, cfg,
                                   positions=positions, causal=False,
                                   kv_input=enc_out)
            return h + a, None
        x, _ = jax.lax.scan(xbody, x, params["xattn"])

    hidden = x
    x = constrain(norm_apply(params["final_norm"], x, cfg.norm_type, cfg.norm_eps), "btd")
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = constrain(linear(x, head, impl=resolve_impl(cfg)), "btv")
    if cfg.padded_vocab_size != cfg.vocab_size:
        # mask the padded vocabulary tail (paper §VI-B vocab padding)
        pad_mask = jnp.arange(cfg.padded_vocab_size) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, jnp.asarray(-1e30, logits.dtype))
    out = (logits, new_caches, aux)
    if return_stats:
        out += (stats,)
    if return_hidden:
        out += (hidden,)
    return out


# --- loss ----------------------------------------------------------------------------

def softmax_xent(logits, labels, mask=None):
    """Mean next-token cross entropy.  logits: (b, s, v); labels: (b, s)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.clip(jnp.sum(mask), 1.0)


def lm_loss(params, batch, cfg: ModelConfig, remat: str = "none"):
    """batch: dict(tokens, labels[, loss_mask, patch_embeds, encoder_frames]).

    Returns (loss, metrics).  MTP (deepseek) adds a depth-1 future-token loss.
    """
    logits, _, aux, hidden = apply_lm(
        params, batch["tokens"], cfg, remat=remat,
        patch_embeds=batch.get("patch_embeds"),
        encoder_frames=batch.get("encoder_frames"),
        return_hidden=True)
    mask = batch.get("loss_mask")
    npatch = 0 if batch.get("patch_embeds") is None else batch["patch_embeds"].shape[1]
    if npatch:
        logits = logits[:, npatch:]
        hidden = hidden[:, npatch:]
    loss = softmax_xent(logits[:, :-1], batch["labels"][:, 1:],
                        None if mask is None else mask[:, 1:])
    metrics = {"lm_loss": loss}
    if cfg.num_experts:
        metrics["aux_loss"] = aux
        loss = loss + 0.001 * aux
    if cfg.mtp_depth and "mtp" in params:
        dt = compute_dtype(cfg.dtype)
        emb_next = params["embed"][batch["labels"]].astype(dt)
        mtp_in = jnp.concatenate([hidden.astype(dt), emb_next], axis=-1)
        mtp_in = linear(mtp_in, params["mtp"]["proj"], impl=resolve_impl(cfg))
        pos = jnp.arange(mtp_in.shape[1])
        h2, _, _, _ = _apply_core(params["mtp"]["block"], mtp_in, cfg,
                                  "dense", positions=pos)
        h2 = norm_apply(params["mtp"]["norm"], h2, cfg.norm_type, cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
        mtp_logits = linear(h2, head, impl=resolve_impl(cfg))
        mtp = softmax_xent(mtp_logits[:, :-2], batch["labels"][:, 2:])
        metrics["mtp_loss"] = mtp
        loss = loss + 0.3 * mtp
    metrics["loss"] = loss
    return loss, metrics
