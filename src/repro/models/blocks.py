"""Transformer block assembly and scanned layer stacks.

A model is a list of *segments*; each segment is `n` structurally identical
layers whose parameters are stacked on a leading axis and executed with
`jax.lax.scan` (fast compiles + small HLO even for 96-layer models, and the
natural form for per-layer FSDP gathering under SPMD).

Segment kinds:
  dense        — attn + MLP                       (qwen, nemotron, internlm2, ...)
  moe          — attn + MoE                       (deepseek-v3 layers 3..61)
  pair         — [moe, dense] superblock          (llama4: MoE every 2nd layer)
  ssm          — Mamba2 block only                (mamba2-780m)
  hybrid_super — `k` (ssm+MLP) layers + one SHARED attention block
                 (zamba2: shared weights live outside the scan)
Supports sequential and parallel-layers (§VI-C1) residual forms.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import apply_attention, init_attention, mla_cache_width
from .layers import LayerOf, norm_apply, norm_init
from .mlp import apply_mlp, init_mlp
from .moe import (EXPERT_KEYS, apply_moe, apply_moe_dropless, init_moe,
                  merge_moe_stats, no_moe_stats)
from .ssm import apply_ssm, decode_ssm, init_ssm, init_ssm_cache


# --- plan ---------------------------------------------------------------------------

def stack_plan(cfg: ModelConfig) -> List[Tuple[str, int]]:
    L = cfg.num_layers
    if cfg.family == "ssm":
        return [("ssm", L)]
    if cfg.family == "hybrid":
        k = cfg.hybrid_attn_every or L
        assert L % k == 0, "hybrid: L must divide hybrid_attn_every"
        return [("hybrid_super", L // k)]
    if cfg.num_experts:
        if cfg.moe_every == 1:
            fd = cfg.first_dense_layers
            plan: List[Tuple[str, int]] = []
            if fd:
                plan.append(("dense", fd))
            plan.append(("moe", L - fd))
            return plan
        assert cfg.moe_every == 2 and cfg.first_dense_layers == 0
        return [("pair", L // 2)]
    return [("dense", L)]


# --- per-kind init -------------------------------------------------------------------

def _init_one(key, cfg: ModelConfig, kind: str):
    ks = jax.random.split(key, 8)
    if kind == "dense":
        return {"norm1": norm_init(cfg.d_model, cfg.norm_type),
                "attn": init_attention(ks[0], cfg),
                "norm2": norm_init(cfg.d_model, cfg.norm_type),
                "mlp": init_mlp(ks[1], cfg)}
    if kind == "moe":
        return {"norm1": norm_init(cfg.d_model, cfg.norm_type),
                "attn": init_attention(ks[0], cfg),
                "norm2": norm_init(cfg.d_model, cfg.norm_type),
                "moe": init_moe(ks[1], cfg)}
    if kind == "pair":
        return {"moe_blk": _init_one(ks[0], cfg, "moe"),
                "dense_blk": _init_one(ks[1], cfg, "dense")}
    if kind == "ssm":
        return {"norm1": norm_init(cfg.d_model, cfg.norm_type),
                "ssm": init_ssm(ks[0], cfg)}
    if kind == "hybrid_super":
        k = cfg.hybrid_attn_every
        sub = jax.vmap(lambda kk: {
            "norm1": norm_init(cfg.d_model, cfg.norm_type),
            "ssm": init_ssm(kk, cfg),
        })(jax.random.split(ks[0], k))
        return {"layers": sub}
    raise ValueError(kind)


def init_segment(key, cfg: ModelConfig, kind: str, n: int):
    return jax.vmap(lambda k: _init_one(k, cfg, kind))(jax.random.split(key, n))


def init_shared(key, cfg: ModelConfig):
    """Zamba2 shared attention+MLP block (weights tied across applications)."""
    if cfg.family != "hybrid":
        return None
    k1, k2 = jax.random.split(key)
    return {"norm": norm_init(cfg.d_model, cfg.norm_type),
            "attn": init_attention(k1, cfg),
            "norm2": norm_init(cfg.d_model, cfg.norm_type),
            "mlp": init_mlp(k2, cfg)}


# --- caches --------------------------------------------------------------------------

KV_DTYPES = ("auto", "int8")


def kv_cache_dtype(cfg: ModelConfig, dtype):
    """Storage dtype for k/v cache leaves: `cfg.kv_dtype` ("auto" resolves to
    the compute dtype; "int8" stores quantized k/v plus f32 scale leaves)."""
    if cfg.kv_dtype == "auto":
        return dtype
    if cfg.kv_dtype == "int8":
        if cfg.attn_type == "mla":
            raise ValueError(
                "kv_dtype='int8' is not supported with attn_type='mla' "
                "(the latent cache feeds back through projections, not raw k/v)")
        return jnp.int8
    raise ValueError(
        f"unknown kv_dtype {cfg.kv_dtype!r}; valid: {list(KV_DTYPES)}")


def _kv_cache_shape(cfg: ModelConfig, batch: int, s_max: int):
    if cfg.attn_type == "mla":
        return {"latent": (batch, s_max, mla_cache_width(cfg))}
    return {"k": (batch, s_max, cfg.num_kv_heads, cfg.head_dim),
            "v": (batch, s_max, cfg.num_kv_heads, cfg.head_dim)}


def init_cache_segment(cfg: ModelConfig, kind: str, n: int, batch: int,
                       s_max: int, dtype=jnp.bfloat16):
    """Cache pytree for one segment (leading dim n, scanned with the layers)."""
    def kv():
        store = kv_cache_dtype(cfg, dtype)
        leaves = {k: jnp.zeros((n,) + shp, store)
                  for k, shp in _kv_cache_shape(cfg, batch, s_max).items()}
        if store == jnp.int8:
            # absmax scale per (token, kv_head): head_dim is the reduce axis
            for name in ("k", "v"):
                leaves[f"{name}_scale"] = jnp.zeros(
                    (n, batch, s_max, cfg.num_kv_heads), jnp.float32)
        return leaves

    if kind in ("dense", "moe"):
        return kv()
    if kind == "pair":
        return {"moe_blk": kv(), "dense_blk": kv()}
    if kind == "ssm":
        c = init_ssm_cache(cfg, batch, dtype)
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), c)
    if kind == "hybrid_super":
        c = init_ssm_cache(cfg, batch, dtype)
        ssm = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n, cfg.hybrid_attn_every) + x.shape), c)
        return {"ssm": ssm, "shared_attn": kv()}
    raise ValueError(kind)


# --- per-kind apply ------------------------------------------------------------------

def _apply_attn_block(p, x, cfg, positions, cache, cache_index,
                      block_tables=None):
    h, new_cache = apply_attention(
        p["attn"], norm_apply(p["norm1"], x, cfg.norm_type, cfg.norm_eps), cfg,
        positions=positions, cache=cache, cache_index=cache_index,
        block_tables=block_tables)
    return h, new_cache


def _apply_core(p, x, cfg: ModelConfig, kind: str, *, positions,
                cache=None, cache_index=None, shared=None, decode=False,
                block_tables=None, token_mask=None):
    """One layer.  Returns (x, new_cache, aux_loss, moe_stats); moe_stats
    (`moe.MOE_STATS` int32) is counted by the drop-free MoE path, zeros
    elsewhere.  token_mask (b, s): rows that hold a token (drop-free MoE
    routes no other row)."""
    aux = jnp.zeros((), jnp.float32)
    stats = no_moe_stats()
    if kind in ("dense", "moe"):
        if cfg.seq_parallel and x.shape[1] > 1:
            from ..parallel.sharding import constrain
            x = constrain(x, "btd_sp")
        attn_out, new_cache = _apply_attn_block(p, x, cfg, positions, cache,
                                                cache_index, block_tables)
        if cfg.parallel_layers:
            # y = x + Attn(N(x)) + MLP(N(x))   (§VI-C1; same first norm)
            mix_in = norm_apply(p["norm1"], x, cfg.norm_type, cfg.norm_eps)
        else:
            x = x + attn_out
            mix_in = norm_apply(p["norm2"], x, cfg.norm_type, cfg.norm_eps)
        if kind == "moe":
            if cfg.moe_dispatch == "shard_map":
                from .moe_shardmap import apply_moe_shardmap
                mix_out, aux = apply_moe_shardmap(p["moe"], mix_in, cfg)
            elif cfg.moe_dispatch == "dropless":
                mix_out, stats = apply_moe_dropless(p["moe"], mix_in, cfg,
                                                    token_mask)
            else:
                mix_out, aux = apply_moe(p["moe"], mix_in, cfg)
        else:
            mix_out = apply_mlp(p["mlp"], mix_in, cfg)
        x = x + mix_out + (attn_out if cfg.parallel_layers else 0)
        return x, new_cache, aux, stats

    if kind == "pair":
        x, c1, a1, stats = _apply_core(
            p["moe_blk"], x, cfg, "moe", positions=positions,
            cache=None if cache is None else cache["moe_blk"],
            cache_index=cache_index, decode=decode,
            block_tables=block_tables, token_mask=token_mask)
        x, c2, a2, _ = _apply_core(
            p["dense_blk"], x, cfg, "dense", positions=positions,
            cache=None if cache is None else cache["dense_blk"],
            cache_index=cache_index, decode=decode,
            block_tables=block_tables)
        nc = None if cache is None else {"moe_blk": c1, "dense_blk": c2}
        return x, nc, a1 + a2, stats

    if kind == "ssm":
        xin = norm_apply(p["norm1"], x, cfg.norm_type, cfg.norm_eps)
        if decode:
            y, new_c = decode_ssm(p["ssm"], xin, cfg, cache)
        else:
            y, (st, tails) = apply_ssm(p["ssm"], xin, cfg,
                                       state=None if cache is None else cache["state"])
            new_c = None if cache is None else jax.tree.map(
                lambda old, new: new.astype(old.dtype),
                cache, {"state": st, **tails})
        return x + y, new_c, aux, stats

    if kind == "hybrid_super":
        assert block_tables is None, \
            "block-table KV paging does not support ssm/hybrid caches"
        k = cfg.hybrid_attn_every
        new_ssm = [] if cache is not None else None
        for i in range(k):
            pi = jax.tree.map(lambda t: t[i], p["layers"])
            ci = None if cache is None else jax.tree.map(lambda t: t[i], cache["ssm"])
            xin = norm_apply(pi["norm1"], x, cfg.norm_type, cfg.norm_eps)
            if decode:
                y, nc = decode_ssm(pi["ssm"], xin, cfg, ci)
            else:
                y, (st, tails) = apply_ssm(pi["ssm"], xin, cfg,
                                           state=None if ci is None else ci["state"])
                nc = None if ci is None else jax.tree.map(
                    lambda old, new: new.astype(old.dtype),
                    ci, {"state": st, **tails})
            x = x + y
            if cache is not None:
                new_ssm.append(nc)
        # shared attention+MLP block (weights tied across all applications)
        sc = None if cache is None else cache["shared_attn"]
        attn_out, new_kv = apply_attention(
            shared["attn"],
            norm_apply(shared["norm"], x, cfg.norm_type, cfg.norm_eps), cfg,
            positions=positions, cache=sc, cache_index=cache_index)
        x = x + attn_out
        x = x + apply_mlp(
            shared["mlp"],
            norm_apply(shared["norm2"], x, cfg.norm_type, cfg.norm_eps), cfg)
        if cache is None:
            return x, None, aux, stats
        stacked_ssm = jax.tree.map(lambda *ts: jnp.stack(ts), *new_ssm)
        return x, {"ssm": stacked_ssm, "shared_attn": new_kv}, aux, stats

    raise ValueError(kind)


# --- scanned stack -------------------------------------------------------------------

def apply_stack(segments_params, cfg: ModelConfig, x, *, positions,
                caches=None, cache_index=None, decode=False, shared=None,
                remat: str = "none", block_tables=None, token_mask=None,
                return_stats: bool = False):
    """Run all segments.  segments_params: list of (kind, stacked_params).

    caches: list aligned with segments (or None).
    block_tables: (b, max_blocks) block-pool indirection, shared by every
    layer (scan-closure captured — all layers' kv leaves use one table).
    With it every cache leaf is a block pool stacked with layers first.
    token_mask: (b, s) rows holding a token (drop-free MoE).
    Returns (x, new_caches, total_aux), and the layers' merged MoE stats
    last with return_stats.
    """
    total_aux = jnp.zeros((), jnp.float32)
    stats = no_moe_stats()
    new_caches = [] if caches is not None else None
    # a block pool (GQA k/v and int8 scales, or MLA's latent) rides whole in
    # the scan's carry, and the drop-free MoE layers' expert weights stay
    # whole outside it: layer i's token is written into the stack in place
    # and the kernels read layer i in place (`LayerOf`), where the scan's
    # slices would copy each layer's pool and weights out, the pool back
    # into a fresh stack, and that stack once more onto the donated pool
    carry_pool = block_tables is not None

    for si, (kind, sp) in enumerate(segments_params):
        seg_cache = None if caches is None else caches[si]
        whole = {}
        if kind == "moe" and cfg.moe_dispatch == "dropless":
            whole = {k: v for k, v in sp["moe"].items() if k in EXPERT_KEYS}
            sp = {**sp, "moe": {k: v for k, v in sp["moe"].items()
                                if k not in EXPERT_KEYS}}

        def body(carry, xs, _kind=kind, _whole=whole):
            h, aux, st, pool = carry
            p_l, c_l, i = xs
            if _whole:
                p_l = {**p_l, "moe": {**p_l["moe"], **{
                    k: LayerOf(v, i) for k, v in _whole.items()}}}
            if pool is not None:
                c_l = jax.tree.map(lambda t: LayerOf(t, i), pool)
            h, nc, a, s_l = _apply_core(
                p_l, h, cfg, _kind, positions=positions, cache=c_l,
                cache_index=cache_index, shared=shared, decode=decode,
                block_tables=block_tables, token_mask=token_mask)
            if pool is not None:
                pool, nc = jax.tree.map(
                    lambda t: t.stack, nc,
                    is_leaf=lambda t: isinstance(t, LayerOf)), None
            return (h, aux + a, merge_moe_stats(st, s_l), pool), nc

        if remat == "full":
            body = jax.checkpoint(body, prevent_cse=False)
        elif remat == "dots":
            body = jax.checkpoint(
                body, prevent_cse=False,
                policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

        n = jax.tree.leaves(sp)[0].shape[0]
        pool = seg_cache if carry_pool else None
        xs = (sp, None if carry_pool else seg_cache, jnp.arange(n))
        (x, total_aux, stats, pool), seg_new_cache = jax.lax.scan(
            body, (x, total_aux, stats, pool), xs)
        if new_caches is not None:
            new_caches.append(pool if carry_pool else seg_new_cache)
    if return_stats:
        return x, new_caches, total_aux, stats
    return x, new_caches, total_aux
