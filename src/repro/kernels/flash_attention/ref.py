"""Pure-jnp oracles for the flash-attention and paged-decode kernels."""
import jax
import jax.numpy as jnp

NEG_INF = -1e30


def attention_ref(q, k, v, causal: bool = True, scale=None):
    """q: (bh, sq, d); k, v: (bkv, skv, d); GQA via bh % bkv == 0."""
    bh, sq, d = q.shape
    bkv, skv, _ = k.shape
    g = bh // bkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    k = jnp.repeat(k, g, axis=0)
    v = jnp.repeat(v, g, axis=0)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, skv), bool), k=skv - sq)
        s = jnp.where(mask[None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqk,bkd->bqd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_decode_ref(q, k_pool, v_pool, slot_idx, lengths, scale=None):
    """q: (b, a, d); k_pool, v_pool: (slots, s_max, nkv, d); slot_idx: (b,)
    row->slot gather; lengths: (b,) live kv entries per row (0 = dead slot,
    returns zeros).  GQA via a % nkv == 0.  Returns (b, a, d)."""
    b, a, d = q.shape
    _, s_max, nkv, _ = k_pool.shape
    g = a // nkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    k = k_pool[slot_idx].transpose(0, 2, 1, 3)  # (b, nkv, s_max, d)
    v = v_pool[slot_idx].transpose(0, 2, 1, 3)
    qh = q.reshape(b, nkv, g, d)
    s = jnp.einsum("bhgd,bhsd->bhgs", qh.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    live = jnp.arange(s_max)[None, :] < lengths[:, None]  # (b, s_max)
    s = jnp.where(live[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", w, v.astype(jnp.float32))
    out = jnp.where((lengths > 0)[:, None, None, None], out, 0.0)
    return out.reshape(b, a, d).astype(q.dtype)


def gather_block_kv(pool, block_tables):
    """(num_blocks, bs, nkv, d) pool + (b, max_blocks) tables -> contiguous
    (b, max_blocks*bs, nkv, d) per-row KV (logical layout)."""
    g = pool[block_tables]                       # (b, max_nb, bs, nkv, d)
    b, max_nb, bs = g.shape[:3]
    return g.reshape(b, max_nb * bs, *g.shape[3:])


def paged_decode_blocktable_ref(q, k_blocks, v_blocks, block_tables, lengths,
                                scale=None):
    """Oracle for the block-table kernel: gather each row's physical blocks
    into the logical layout, then slot-decode with an identity map."""
    b = q.shape[0]
    k = gather_block_kv(k_blocks, block_tables)
    v = gather_block_kv(v_blocks, block_tables)
    return paged_decode_ref(q, k, v, jnp.arange(b, dtype=jnp.int32), lengths,
                            scale=scale)


def mla_decode_ref(q, latent, lengths, *, v_dim: int, scale: float):
    """Absorbed MLA decode over contiguous per-row latents.  q: (b, a, c)
    latent-space queries; latent: (b, s, c); lengths: (b,) live entries
    (0 = dead row, returns zeros).  Returns (b, a, v_dim): p . latent[...,
    :v_dim] per head."""
    s = jnp.einsum("bac,bsc->bas", q.astype(jnp.float32),
                   latent.astype(jnp.float32)) * scale
    live = jnp.arange(latent.shape[1])[None, :] < lengths[:, None]
    s = jnp.where(live[:, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bas,bsc->bac", w,
                     latent[..., :v_dim].astype(jnp.float32))
    out = jnp.where((lengths > 0)[:, None, None], out, 0.0)
    return out.astype(q.dtype)


def paged_mla_decode_ref(q, latent_blocks, block_tables, lengths, *,
                         v_dim: int, scale: float):
    """Oracle for the MLA block-table kernel: gather each row's latent
    pages into the logical layout, then `mla_decode_ref`."""
    return mla_decode_ref(q, gather_block_kv(latent_blocks, block_tables),
                          lengths, v_dim=v_dim, scale=scale)
