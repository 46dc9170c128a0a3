"""Attention variants: GQA/MHA (+QKV bias) and DeepSeek-V3 MLA.

Shapes follow the paper's Table II GEMM decomposition exactly:
  qkv:    (b*s, h) x (h, (a+2kv)*hd)
  score:  b*a BMMs of (s, hd) x (hd, s_kv)
  aov:    b*a BMMs of (s, s_kv) x (s_kv, hd)
  out:    (b*s, a*hd) x (a*hd, h)

Both a fused-reference path (jnp einsum, used on CPU and in the dry-run) and
the Pallas flash-attention path (TPU target) are provided; dispatch is by
`use_flash`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import (LayerOf, apply_rotary, dense_init, norm_apply, norm_init,
                     rope_inv_freq, rope_magnitude, yarn_softmax_scale)
from .linear import linear, resolve_impl

NEG_INF = -1e30


def init_gqa(key, cfg: ModelConfig):
    h, hd = cfg.d_model, cfg.head_dim
    a, kv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], h, a * hd),
        "wk": dense_init(ks[1], h, kv * hd),
        "wv": dense_init(ks[2], h, kv * hd),
        "wo": dense_init(ks[3], a * hd, h, scale=1.0 / (2 * cfg.num_layers) ** 0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((a * hd,), jnp.float32)
        p["bk"] = jnp.zeros((kv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((kv * hd,), jnp.float32)
    return p


def _sdpa(q, k, v, causal: bool, q_pos=None, kv_len=None,
          seq_sharded: bool = False):
    """Reference scaled-dot-product attention.

    q: (b, sq, a, hd); k, v: (b, skv, kv, hd).  GQA: a % kv == 0.
    q_pos: (sq,) absolute positions of the queries (for causal masking
    against a cache), or (b, sq) per-row positions (serving-engine slots at
    heterogeneous depths); kv_len: number of valid cache entries (scalar, or
    (b,) per-row).

    seq_sharded (decode): anchors K/V and the score matrix sequence-sharded
    on the model axis — the softmax then reduces over a sharded dim, which
    XLA lowers to partial max/sum + tiny all-reduces (distributed
    flash-decode) instead of gathering the 32k-deep cache per layer.
    """
    from ..parallel.sharding import constrain
    b, sq, a, hd = q.shape
    skv, nkv = k.shape[1], k.shape[2]
    g = a // nkv
    if seq_sharded:
        k = constrain(k, "bskh")
        v = constrain(v, "bskh")
    q = q.reshape(b, sq, nkv, g, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32)
    if seq_sharded:
        scores = constrain(scores, "bkgqs")
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    kv_pos = jnp.arange(skv)
    mask = None  # (B, sq, skv) with B in {1, b}, broadcast over head dims
    if causal:
        if q_pos is None:
            q_pos = jnp.arange(sq)
        if q_pos.ndim == 1:
            mask = (kv_pos[None, :] <= q_pos[:, None])[None]
        else:  # per-row query positions
            mask = kv_pos[None, None, :] <= q_pos[:, :, None]
    if kv_len is not None:
        kvl = jnp.asarray(kv_len)
        live = (kv_pos[None, :] < kvl[:, None])[:, None, :] if kvl.ndim \
            else (kv_pos < kvl)[None, None, :]
        mask = live if mask is None else mask & live
    if mask is not None:
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v)
    return out.reshape(b, sq, a, v.shape[-1])  # v head dim may differ (MLA)


def pool_write(cache, new, block_tables, ci):
    """Write one token a row into the leaves of a block pool, in place.

    cache: dict of leaves (num_blocks, block_size, ...), or of `LayerOf` the
    stacks of every layer's pool (layers, num_blocks, block_size, ...);
    new: the same keys, (b, 1, ...) each row's token; ci: (b,) each row's
    write position, which lands at (block_tables[b, ci // bs], ci % bs) of
    the layer.  Each live row's tail block is private by the pool's
    copy-on-write discipline, so rows never collide (dead rows all write
    the pool's garbage block, which is never read).  Returns the leaves as
    given, `LayerOf` or plain."""
    out = {}
    for n, leaf in cache.items():
        stack, layer = (leaf if isinstance(leaf, LayerOf)
                        else LayerOf(leaf[None], 0))
        blk = stack.shape[2]
        phys = block_tables[jnp.arange(ci.shape[0]), ci // blk]
        stack = stack.at[layer, phys, ci % blk].set(
            new[n][:, 0].astype(stack.dtype))
        out[n] = LayerOf(stack, layer) if isinstance(leaf, LayerOf) \
            else stack[0]
    return out


def _layer(leaf):
    """The one layer's pool a `pool_write` leaf holds."""
    return leaf.stack[leaf.index] if isinstance(leaf, LayerOf) else leaf


def apply_gqa(p, x, cfg: ModelConfig, *, positions, causal=True,
              cache=None, cache_index=None, kv_input=None,
              block_tables=None):
    """x: (b, s, h).  Returns (out, new_cache).

    cache: dict(k=(b, s_max, kv, hd), v=...) or None.
    cache_index: scalar write offset into a contiguous cache (prefill, and
    decode at one shared depth), or with block_tables a (b,) vector of
    per-row offsets; `positions` should then be the matching (b, 1) per-row
    positions.
    kv_input: if set, keys/values come from this tensor (cross-attention).
    block_tables: (b, max_blocks) int32 — switches the cache to the
    serving engine's *block-pool* layout (k/v: (num_blocks, block_size, kv,
    hd), or `LayerOf` the stack of every layer's pool, read and written in
    place): row b's logical kv block j lives in physical block
    `block_tables[b, j]`.  Requires single-token decode with a (b,) vector
    cache_index; the new token is written by `pool_write`.  Attention then
    runs the Pallas paged kernel when cfg.attn_impl == "paged", else the
    jnp reference over each row's gathered blocks.
    """
    b, s, h = x.shape
    a, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    impl = resolve_impl(cfg)
    src = x if kv_input is None else kv_input
    q = linear(x, p["wq"], impl=impl)
    k = linear(src, p["wk"], impl=impl)
    v = linear(src, p["wv"], impl=impl)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(b, s, a, hd)
    k = k.reshape(b, src.shape[1], nkv, hd)
    v = v.reshape(b, src.shape[1], nkv, hd)
    if cfg.pos_emb == "rotary" and kv_input is None:
        q = apply_rotary(q, positions, cfg.rope_theta)
        k = apply_rotary(k, positions, cfg.rope_theta)
    new_cache = None
    kv_len = None
    # int8 KV cache (cfg.kv_dtype="int8"): quantize per (token, kv_head) on
    # write; dequantize on read (jnp paths) or in-kernel (paged kernels)
    quant = cache is not None and "k_scale" in cache
    k_scale = v_scale = None
    if block_tables is not None:
        assert cache is not None and kv_input is None
        ci = jnp.asarray(cache_index)
        assert s == 1 and ci.ndim == 1, \
            "block_tables requires single-token decode with vector cache_index"
        new = {"k": k, "v": v}
        if quant:
            from ..quant import dequantize_kv, quantize_kv
            (kq, ks), (vq, vs) = quantize_kv(k), quantize_kv(v)
            new = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
        new_cache = pool_write(cache, new, block_tables, ci)
        lengths = (ci + 1).astype(jnp.int32)
        if cfg.attn_impl == "paged":
            from ..kernels.flash_attention.ops import paged_decode_blocktable
            out = paged_decode_blocktable(
                q[:, 0], new_cache["k"], new_cache["v"], block_tables,
                lengths, k_scale=new_cache.get("k_scale"),
                v_scale=new_cache.get("v_scale"), tuned=True)[:, None]
        else:
            from ..kernels.flash_attention.ref import gather_block_kv
            kg = gather_block_kv(_layer(new_cache["k"]), block_tables)
            vg = gather_block_kv(_layer(new_cache["v"]), block_tables)
            if quant:
                kg = dequantize_kv(kg, gather_block_kv(
                    _layer(new_cache["k_scale"]), block_tables), q.dtype)
                vg = dequantize_kv(vg, gather_block_kv(
                    _layer(new_cache["v_scale"]), block_tables), q.dtype)
            out = _sdpa(q, kg.astype(q.dtype), vg.astype(q.dtype),
                        causal=causal, q_pos=positions, kv_len=lengths)
        out = linear(out.reshape(b, s, a * hd), p["wo"], impl=impl)
        return out, new_cache
    if cache is not None and kv_input is None:
        ci = jnp.asarray(cache_index)
        assert ci.ndim == 0, \
            "a per-row (vector) cache_index needs block_tables (block pool)"
        upd = jax.lax.dynamic_update_slice_in_dim
        if quant:
            from ..quant import dequantize_kv, quantize_kv
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            kq = upd(cache["k"], kq, ci, axis=1)
            vq = upd(cache["v"], vq, ci, axis=1)
            k_scale = upd(cache["k_scale"], ks, ci, axis=1)
            v_scale = upd(cache["v_scale"], vs, ci, axis=1)
            new_cache = {"k": kq, "v": vq,
                         "k_scale": k_scale, "v_scale": v_scale}
            k = dequantize_kv(kq, k_scale, q.dtype)
            v = dequantize_kv(vq, v_scale, q.dtype)
        else:
            k = upd(cache["k"], k.astype(cache["k"].dtype), ci, axis=1)
            v = upd(cache["v"], v.astype(cache["v"].dtype), ci, axis=1)
            new_cache = {"k": k, "v": v}
        kv_len = ci + s
    # 2-D positions are per-row query positions; _sdpa masks them row-wise
    q_pos = positions
    is_decode = cache is not None and s == 1
    if cfg.attn_impl == "flash" and not is_decode and cache is None:
        # Pallas flash kernel with its custom-VJP fused backward: the
        # training/prefill fast path.  Cache-backed prefill (dynamic kv_len)
        # and decode stay on the jnp paths below; MLA never routes here.
        from ..kernels.flash_attention.ops import flash_attention
        out = flash_attention(q, k.astype(q.dtype), v.astype(q.dtype),
                              causal=causal and kv_input is None, tuned=True)
    elif cfg.attn_impl == "blocked" and not is_decode:
        from .blocked_attention import blocked_sdpa
        out = blocked_sdpa(q, k.astype(q.dtype), v.astype(q.dtype),
                           causal=causal and kv_input is None,
                           q_pos=q_pos if q_pos.ndim == 1 else q_pos[0],
                           kv_len=kv_len, block_kv=cfg.attn_block_kv)
    else:
        out = _sdpa(q, k.astype(q.dtype), v.astype(q.dtype),
                    causal=causal and kv_input is None,
                    q_pos=q_pos, kv_len=kv_len, seq_sharded=is_decode)
    out = linear(out.reshape(b, s, a * hd), p["wo"], impl=impl)
    return out, new_cache


# --- DeepSeek-V3 Multi-head Latent Attention ------------------------------------------

def init_mla(key, cfg: ModelConfig):
    h = cfg.d_model
    a = cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_down": dense_init(ks[0], h, qr),
        "q_norm": norm_init(qr),
        "wq_up": dense_init(ks[1], qr, a * (nope + rope)),
        "wkv_down": dense_init(ks[2], h, kvr + rope),
        "kv_norm": norm_init(kvr),
        "wk_up": dense_init(ks[3], kvr, a * nope),
        "wv_up": dense_init(ks[4], kvr, a * vd),
        "wo": dense_init(ks[5], a * vd, h, scale=1.0 / (2 * cfg.num_layers) ** 0.5),
    }


def mla_cache_width(cfg: ModelConfig) -> int:
    """Values cached per token: c_kv ++ k_rope, zero-padded to whole
    128-lane tiles (576 -> 640 for deepseek-v3).  The device stores a
    576-wide minor dim padded to 640 anyway; naming the pad lets the decode
    kernel copy whole pages, which a 576-wide slice of the padded pool is
    not."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_dim) // 128) * 128


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(qk_nope + qk_rope), times YaRN's mscale^2 where it applies."""
    return yarn_softmax_scale(cfg) / (cfg.qk_nope_dim + cfg.qk_rope_dim) ** 0.5


def _mla_rope(x, positions, cfg: ModelConfig):
    return apply_rotary(x, positions, cfg.rope_theta,
                        inv_freq=rope_inv_freq(cfg, cfg.qk_rope_dim),
                        magnitude=rope_magnitude(cfg))


def _mla_latent_q(p, x, cfg: ModelConfig, positions):
    """Queries (q_nope (b, s, a, nope), roped q_rope (b, s, a, rope)) and
    the latent to cache, normalised c_kv ++ roped k_rope ++ zeros
    (b, s, `mla_cache_width`)."""
    b, s, _ = x.shape
    a, nope, kvr = cfg.num_heads, cfg.qk_nope_dim, cfg.kv_lora_rank
    impl = resolve_impl(cfg)
    cq = norm_apply(p["q_norm"], linear(x, p["wq_down"], impl=impl),
                    eps=cfg.norm_eps)
    q = linear(cq, p["wq_up"], impl=impl).reshape(b, s, a, -1)
    q_nope, q_rope = q[..., :nope], _mla_rope(q[..., nope:], positions, cfg)
    latent = linear(x, p["wkv_down"], impl=impl)
    c_kv = norm_apply(p["kv_norm"], latent[..., :kvr], eps=cfg.norm_eps)
    k_rope = _mla_rope(latent[..., None, kvr:], positions, cfg)[..., 0, :]
    pad = jnp.zeros(k_rope.shape[:-1] + (mla_cache_width(cfg) - kvr
                                         - cfg.qk_rope_dim,), k_rope.dtype)
    return q_nope, q_rope, jnp.concatenate([c_kv, k_rope, pad], axis=-1)


def _mla_out(p, o, cfg: ModelConfig):
    """Heads' outputs (b, s, a, v_head) -> (b, s, h) through wo."""
    b, s = o.shape[:2]
    return linear(o.reshape(b, s, -1), p["wo"], impl=resolve_impl(cfg))


def _mla_decode(p, q_nope, q_rope, cfg: ModelConfig, *, latent=None,
                pool=None, block_tables=None, lengths):
    """One-token decode in the absorbed form: the cached latent is never
    decompressed.  q_lat = q_nope W_uk per head (kv_lora wide), scores
    [q_lat; q_rope] . [c_kv; k_rope], out_lat = p . c_kv, o = out_lat W_uv.
    Keys come from contiguous per-row `latent` (b, S, c) or from a latent
    block `pool` through `block_tables` (the Pallas kernel when
    cfg.attn_impl == "paged")."""
    from ..kernels.flash_attention.ref import mla_decode_ref
    a, nope, kvr = cfg.num_heads, cfg.qk_nope_dim, cfg.kv_lora_rank
    dt = q_nope.dtype
    wk = p["wk_up"].astype(dt).reshape(kvr, a, nope)
    q_lat = jnp.einsum("ban,can->bac", q_nope[:, 0], wk)
    pad = jnp.zeros(q_lat.shape[:2] + (mla_cache_width(cfg) - kvr
                                       - cfg.qk_rope_dim,), dt)
    q = jnp.concatenate([q_lat, q_rope[:, 0], pad], axis=-1)   # (b, a, c)
    scale = mla_softmax_scale(cfg)
    if block_tables is None:
        out_lat = mla_decode_ref(q, latent.astype(dt), lengths, v_dim=kvr,
                                 scale=scale)
    else:
        from ..kernels.flash_attention.ops import paged_mla_decode
        out_lat = paged_mla_decode(q, pool, block_tables, lengths, v_dim=kvr,
                                   scale=scale,
                                   use_pallas=cfg.attn_impl == "paged")
    wv = p["wv_up"].astype(dt).reshape(kvr, a, cfg.v_head_dim)
    return _mla_out(p, jnp.einsum("bac,cav->bav", out_lat, wv)[:, None], cfg)


def _query_chunk(s: int, a: int, skv: int) -> int:
    """Queries per chunk of the prefill attention: the largest divisor of s
    whose (a, chunk, skv) f32 score tile stays within 32M elements."""
    want = max(1, (1 << 25) // max(a * skv, 1))
    return max(c for c in range(1, min(s, want) + 1) if s % c == 0)


def _mla_prefill(p, q_nope, q_rope, lat, cfg: ModelConfig, *, q_pos,
                 kv_len=None):
    """Decompressed attention for prefill and training: k_nope = c_kv W_uk,
    v = c_kv W_uv over every key, the queries taken a chunk at a time so
    no (heads, s, s_kv) score tensor is built.  lat: (b, S, c) keys;
    q_pos: (s,) query positions (causal against key positions); kv_len:
    live keys (None = all)."""
    b, s, a, nope = q_nope.shape
    skv, kvr = lat.shape[1], cfg.kv_lora_rank
    impl = resolve_impl(cfg)
    dt = q_nope.dtype
    c_kv = lat[..., :kvr].astype(dt)
    k_rope = lat[..., kvr:kvr + cfg.qk_rope_dim].astype(dt)
    k_nope = linear(c_kv, p["wk_up"], impl=impl).reshape(b, skv, a, nope)
    v = linear(c_kv, p["wv_up"], impl=impl).reshape(b, skv, a, -1)
    scale = mla_softmax_scale(cfg)
    kv_pos = jnp.arange(skv)
    live = kv_pos < (skv if kv_len is None else kv_len)
    c = _query_chunk(s, a, skv)

    def chunk(args):
        qn, qr, qp = args                       # (b, c, a, *), (c,)
        sc = (jnp.einsum("bqan,bkan->baqk", qn, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqar,bkr->baqk", qr, k_rope,
                           preferred_element_type=jnp.float32)) * scale
        ok = (kv_pos[None, :] <= qp[:, None]) & live[None, :]
        w = jax.nn.softmax(jnp.where(ok, sc, NEG_INF), axis=-1)
        return jnp.einsum("baqk,bkav->bqav", w.astype(dt), v)

    def split(x):
        return jnp.moveaxis(x.reshape(b, s // c, c, *x.shape[2:]), 1, 0)

    out = jax.lax.map(chunk, (split(q_nope), split(q_rope),
                              q_pos.reshape(s // c, c)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, a, -1)


def apply_mla(p, x, cfg: ModelConfig, *, positions, cache=None,
              cache_index=None, block_tables=None):
    """MLA with a latent cache of normalised c_kv ++ roped k_rope per token
    (zero-padded to `mla_cache_width`).

    cache: dict(latent=(b, s_max, width)) with a scalar cache_index (a
    write at [ci, ci+s)); with block_tables the cache is a latent block
    pool (num_blocks, block_size, width) or `LayerOf` the stack of every
    layer's pool (written by `pool_write`), row b's logical block j is
    physical block block_tables[b, j], and a (b,) cache_index gives each
    row's write position for one-token decode.  One-token decode runs the
    absorbed form (`_mla_decode`); prefill and training the decompressed
    form with query chunks (`_mla_prefill`).
    """
    b, s, _ = x.shape
    q_nope, q_rope, lat = _mla_latent_q(p, x, cfg, positions)
    if cache is None:
        q_pos = positions if positions.ndim == 1 else positions[0]
        return _mla_out(p, _mla_prefill(p, q_nope, q_rope, lat, cfg,
                                        q_pos=q_pos), cfg), None
    pool = cache["latent"]
    ci = jnp.asarray(cache_index)
    if block_tables is not None:
        assert s == 1 and ci.ndim == 1, \
            "block_tables requires single-token decode with vector cache_index"
        new_cache = pool_write(cache, {"latent": lat}, block_tables, ci)
        out = _mla_decode(p, q_nope, q_rope, cfg, pool=new_cache["latent"],
                          block_tables=block_tables,
                          lengths=(ci + 1).astype(jnp.int32))
        return out, new_cache
    assert ci.ndim == 0, \
        "a per-row (vector) cache_index needs block_tables (block pool)"
    pool = jax.lax.dynamic_update_slice_in_dim(
        pool, lat.astype(pool.dtype), ci, axis=1)
    if s == 1:
        lengths = jnp.broadcast_to(ci + 1, (b,)).astype(jnp.int32)
        out = _mla_decode(p, q_nope, q_rope, cfg, latent=pool,
                          lengths=lengths)
    else:
        q_pos = positions if positions.ndim == 1 else positions[0]
        out = _mla_out(p, _mla_prefill(p, q_nope, q_rope, pool, cfg,
                                       q_pos=q_pos, kv_len=ci + s), cfg)
    return out, {"latent": pool}


def init_attention(key, cfg: ModelConfig):
    return init_mla(key, cfg) if cfg.attn_type == "mla" else init_gqa(key, cfg)


def apply_attention(p, x, cfg: ModelConfig, **kw):
    if cfg.attn_type == "mla":
        kw.pop("kv_input", None)
        kw.pop("causal", None)
        return apply_mla(p, x, cfg, **kw)
    return apply_gqa(p, x, cfg, **kw)
