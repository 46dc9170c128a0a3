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
from .layers import apply_rotary, dense_init
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


def apply_gqa(p, x, cfg: ModelConfig, *, positions, causal=True,
              cache=None, cache_index=None, kv_input=None,
              block_tables=None):
    """x: (b, s, h).  Returns (out, new_cache).

    cache: dict(k=(b, s_max, kv, hd), v=...) or None.
    cache_index: write offset for decode — a scalar, or a (b,) vector of
    per-row offsets (serving engine: each cache slot at its own depth; the
    write is then a per-row one-hot scatter and requires s == 1, and
    `positions` should be the matching (b, s) per-row positions).
    kv_input: if set, keys/values come from this tensor (cross-attention).
    block_tables: (b, max_blocks) int32 — switches the cache to the
    *block-pool* layout (k/v: (num_blocks, block_size, kv, hd)): row b's
    logical kv block j lives in physical block `block_tables[b, j]`.
    Requires single-token decode with a (b,) vector cache_index; the new
    token is scattered into (table[b, ci//bs], ci % bs) — each live row's
    tail block is private by the pool's copy-on-write discipline, so rows
    never collide (dead rows all write the pool's garbage block, which is
    never read).
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
        blk = cache["k"].shape[1]  # physical block size (tokens)
        rows = jnp.arange(b)
        phys = block_tables[rows, ci // blk]
        off = ci % blk
        if quant:
            from ..quant import dequantize_kv, quantize_kv
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            kc = cache["k"].at[phys, off].set(kq[:, 0])
            vc = cache["v"].at[phys, off].set(vq[:, 0])
            k_scale = cache["k_scale"].at[phys, off].set(ks[:, 0])
            v_scale = cache["v_scale"].at[phys, off].set(vs[:, 0])
            new_cache = {"k": kc, "v": vc,
                         "k_scale": k_scale, "v_scale": v_scale}
        else:
            kc = cache["k"].at[phys, off].set(k[:, 0].astype(cache["k"].dtype))
            vc = cache["v"].at[phys, off].set(v[:, 0].astype(cache["v"].dtype))
            new_cache = {"k": kc, "v": vc}
        lengths = (ci + 1).astype(jnp.int32)
        if cfg.attn_impl == "paged":
            from ..kernels.flash_attention.ops import paged_decode_blocktable
            out = paged_decode_blocktable(
                q[:, 0], kc if quant else kc.astype(q.dtype),
                vc if quant else vc.astype(q.dtype),
                block_tables, lengths, k_scale=k_scale, v_scale=v_scale,
                tuned=True)[:, None]
        else:
            from ..kernels.flash_attention.ref import gather_block_kv
            kg = gather_block_kv(kc, block_tables)
            vg = gather_block_kv(vc, block_tables)
            if quant:
                kg = dequantize_kv(kg, gather_block_kv(k_scale, block_tables),
                                   q.dtype)
                vg = dequantize_kv(vg, gather_block_kv(v_scale, block_tables),
                                   q.dtype)
            out = _sdpa(q, kg.astype(q.dtype), vg.astype(q.dtype),
                        causal=causal, q_pos=positions, kv_len=lengths)
        out = linear(out.reshape(b, s, a * hd), p["wo"], impl=impl)
        return out, new_cache
    if cache is not None and kv_input is None:
        ci = jnp.asarray(cache_index)
        if quant:
            from ..quant import dequantize_kv, quantize_kv
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            if ci.ndim:  # per-row write positions (serving-engine slot pool)
                assert s == 1, "vector cache_index requires single-token decode"
                write = jnp.arange(cache["k"].shape[1]) == ci[:, None]
                sel = write[:, :, None, None]
                kq = jnp.where(sel, kq, cache["k"])
                vq = jnp.where(sel, vq, cache["v"])
                k_scale = jnp.where(write[:, :, None], ks, cache["k_scale"])
                v_scale = jnp.where(write[:, :, None], vs, cache["v_scale"])
            else:
                upd = jax.lax.dynamic_update_slice_in_dim
                kq = upd(cache["k"], kq, cache_index, axis=1)
                vq = upd(cache["v"], vq, cache_index, axis=1)
                k_scale = upd(cache["k_scale"], ks, cache_index, axis=1)
                v_scale = upd(cache["v_scale"], vs, cache_index, axis=1)
            new_cache = {"k": kq, "v": vq,
                         "k_scale": k_scale, "v_scale": v_scale}
            kv_len = ci + s
            if cfg.attn_impl == "paged" and s == 1:
                k, v = kq, vq  # paged kernel dequantizes per kv tile
            else:
                k = dequantize_kv(kq, k_scale, q.dtype)
                v = dequantize_kv(vq, v_scale, q.dtype)
        else:
            if ci.ndim:  # per-row write positions (serving-engine slot pool)
                assert s == 1, "vector cache_index requires single-token decode"
                write = jnp.arange(cache["k"].shape[1]) == ci[:, None]  # (b, s_max)
                sel = write[:, :, None, None]
                k = jnp.where(sel, k.astype(cache["k"].dtype), cache["k"])
                v = jnp.where(sel, v.astype(cache["v"].dtype), cache["v"])
            else:
                k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), cache_index, axis=1)
                v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), cache_index, axis=1)
            new_cache = {"k": k, "v": v}
            kv_len = ci + s
    # 2-D positions are per-row query positions; _sdpa masks them row-wise
    q_pos = positions
    is_decode = cache is not None and s == 1
    if cfg.attn_impl == "paged" and is_decode:
        # Pallas paged decode over the slot pool (identity slot map here;
        # the kernel's gather-by-slot path is exercised by the engine tests)
        from ..kernels.flash_attention.ops import paged_decode
        lengths = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))
        out = paged_decode(q[:, 0], k if quant else k.astype(q.dtype),
                           v if quant else v.astype(q.dtype),
                           jnp.arange(b, dtype=jnp.int32), lengths,
                           k_scale=k_scale, v_scale=v_scale,
                           tuned=True)[:, None]
    elif cfg.attn_impl == "flash" and not is_decode and cache is None:
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
        "wq_up": dense_init(ks[1], qr, a * (nope + rope)),
        "wkv_down": dense_init(ks[2], h, kvr + rope),
        "wk_up": dense_init(ks[3], kvr, a * nope),
        "wv_up": dense_init(ks[4], kvr, a * vd),
        "wo": dense_init(ks[5], a * vd, h, scale=1.0 / (2 * cfg.num_layers) ** 0.5),
    }


def apply_mla(p, x, cfg: ModelConfig, *, positions, cache=None, cache_index=None):
    """MLA with a latent-KV cache.  cache: dict(latent=(b, s_max, kvr+rope)).

    Train/prefill: decompressed path (naive).  The latent (c_kv ++ k_rope) is
    what gets cached; decode recomputes k/v from the cached latent (the
    weight-absorbed schedule is an optimization we model in core/, the
    computation here is mathematically identical).
    """
    b, s, h = x.shape
    a = cfg.num_heads
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    impl = resolve_impl(cfg)
    q = linear(linear(x, p["wq_down"], impl=impl), p["wq_up"], impl=impl)
    q = q.reshape(b, s, a, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rotary(q_rope, positions, cfg.rope_theta)

    latent = linear(x, p["wkv_down"], impl=impl)  # (b, s, kvr+rope)
    c_kv, k_rope_flat = latent[..., :kvr], latent[..., kvr:]
    k_rope = apply_rotary(k_rope_flat[..., None, :], positions, cfg.rope_theta)

    kv_len = None
    new_cache = None
    if cache is not None:
        lat_all = jnp.concatenate([c_kv, k_rope[..., 0, :]], axis=-1)
        stored = jax.lax.dynamic_update_slice_in_dim(
            cache["latent"], lat_all.astype(cache["latent"].dtype), cache_index, axis=1)
        new_cache = {"latent": stored}
        c_kv = stored[..., :kvr].astype(x.dtype)
        k_rope = stored[..., None, kvr:].astype(x.dtype)
        kv_len = cache_index + s

    skv = c_kv.shape[1]
    k_nope = linear(c_kv, p["wk_up"], impl=impl).reshape(b, skv, a, nope)
    v = linear(c_kv, p["wv_up"], impl=impl).reshape(b, skv, a, vd)

    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, skv, a, rope))], axis=-1)
    out = _sdpa(q_full, k_full, v, causal=True,
                q_pos=positions[0] if positions.ndim > 1 else positions,
                kv_len=kv_len,
                seq_sharded=(cache is not None and s == 1))
    out = linear(out.reshape(b, s, a * vd), p["wo"], impl=impl)
    return out, new_cache


def init_attention(key, cfg: ModelConfig):
    return init_mla(key, cfg) if cfg.attn_type == "mla" else init_gqa(key, cfg)


def apply_attention(p, x, cfg: ModelConfig, **kw):
    if cfg.attn_type == "mla":
        kw.pop("kv_input", None)
        kw.pop("causal", None)
        assert kw.pop("block_tables", None) is None, \
            "block-table KV paging is not supported for MLA"
        return apply_mla(p, x, cfg, **kw)
    return apply_gqa(p, x, cfg, **kw)
