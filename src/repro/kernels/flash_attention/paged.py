"""Paged decode-attention Pallas TPU kernels for the serving engine's
block pool.

`paged_decode_blocktable_pallas` is vLLM's paged attention at a real page
size: K/V live in a pool of physical blocks (pages) of `block_size` tokens
(`serving.engine.kv_pool.PagedPool`) and row b's logical page j is physical
page `block_table[b, j]`.  One query token per row.  The grid is (b,), one
step per row, so the work follows each row's live pages and not the
table's width.  The table, lengths and layer index sit in SMEM (scalar
prefetch); the pools stay in HBM (`memory_space=pl.ANY`), K and V as the
stack of every layer's pool, read at the layer in place.  A row loops over
chunks of P pages (`blocktable_chunk_pages`: 256 tokens by default,
`block_kv` tokens rounded up to whole pages when given) for
ceil(lengths[b] / chunk) iterations -- none for a dead row, which writes
zeros.  Each chunk's live
pages are gathered by one async copy per page and pool into a
double-buffered VMEM scratch, the next chunk's copies started before this
chunk is computed; pages past the live prefix (a released row's stale
bytes, the garbage block dead rows point at) are never copied.  The scores
of a chunk are one lane-dense (a, chunk * nkv) tile for all query heads
(see `_paged_bt_kernel`), masked by head and by `lengths[b]`, with the
online softmax in f32.  An int8 pool's scale pages travel with their K/V
pages.  The tuner sweeps the page size jointly with the chunk length
(`tuning.search.autotune_paged_decode_blocktable`).

`paged_mla_decode_pallas` is the MLA twin over a latent block pool: the
same page loop (`_live_chunks`), one pool of (block_size, kv_lora + rope)
pages, and all query heads of a row in one (a, chunk) score tile against
the one latent head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30

CHUNK_TOKENS = 256          # default chunk of the block-table kernel
CHUNK_VMEM_BYTES = 8 << 20  # cap on its double-buffered chunk scratch


def blocktable_chunk_pages(block_size: int, max_blocks: int,
                           page_bytes: int,
                           block_kv: int | None = None) -> int:
    """Pages the block-table kernel gathers per chunk: `block_kv` tokens
    (default CHUNK_TOKENS) rounded up to whole pages, capped at max_blocks
    and so that two slots of every gathered pool fit CHUNK_VMEM_BYTES.
    page_bytes is one page of all gathered pools (K, V and any scales)."""
    want = -(-(block_kv or CHUNK_TOKENS) // block_size)
    fit = CHUNK_VMEM_BYTES // (2 * page_bytes)
    return max(1, min(want, max_blocks, fit))


def _live_chunks(table_ref, pools_hbm, bufs, sem, *, row, length: int,
                 pages: int, block_size: int, body, init):
    """The page loop the block-table kernels share: run `body(i, slot,
    carry)` for each chunk i of `pages` pages of `row`'s live prefix, with
    the chunk's pages of every pool already gathered into `bufs[n][slot]`.

    One async copy per live page and pool, double-buffered: chunk i + 1's
    copies start before chunk i is computed; pages past the live prefix are
    never copied, and a dead row (length 0) runs no iteration.  A pool is
    either a (num_blocks, rows, d) page pool, whose page lands as `rows`
    rows of its (2, pages * rows, d) buffer, or a (num_blocks, rows) row of
    per-token scales, landing as row p of its (2, pages, rows) buffer."""
    from jax.experimental.pallas import tpu as pltpu
    n_pages = pl.cdiv(length, block_size)
    n_chunks = pl.cdiv(length, pages * block_size)

    @pl.when(row == 0)
    def _zero_buffers():
        # pages past a row's live prefix are never copied in, so the tail of
        # a last chunk holds what an earlier chunk left (live pages, finite)
        # or these zeros -- never uninitialised memory, which a probability
        # of 0 would still turn into NaN in p @ v
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)

    def copies(i, slot, op):
        # a slot's copies of one pool all signal one semaphore, and each
        # wait takes one page's bytes
        for p in range(pages):
            j = i * pages + p

            @pl.when(j < n_pages)
            def _copy():
                page = table_ref[row, j]
                for n, (src, buf) in enumerate(zip(pools_hbm, bufs)):
                    if len(src.shape) == 3:     # page rows
                        r = src.shape[1]
                        src, dst = src.at[page], buf.at[slot, pl.ds(p * r, r)]
                    else:                       # one row of scales
                        src, dst = src.at[pl.ds(page, 1)], buf.at[
                            slot, pl.ds(p, 1)]
                    getattr(pltpu.make_async_copy(src, dst, sem.at[n, slot]),
                            op)()

    @pl.when(n_chunks > 0)
    def _prefetch_first():
        copies(0, 0, "start")

    def step(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_chunks)
        def _prefetch_next():
            copies(i + 1, 1 - slot, "start")

        copies(i, slot, "wait")
        return body(i, slot, carry)

    return jax.lax.fori_loop(0, n_chunks, step, init)


def _online_softmax(s, carry, v, pv_scale=None):
    """One chunk of the online softmax: scores s (rows, cols) f32, values v
    (cols, dv).  carry is (m, l, acc); pv_scale multiplies the
    probabilities before p @ v (int8 value scales)."""
    m_prev, l_prev, acc_prev = carry
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    if pv_scale is not None:
        p = p * pv_scale
    acc_new = acc_prev * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _softmax_init(rows: int, dv: int):
    return (jnp.full((rows, 1), NEG_INF, jnp.float32),
            jnp.zeros((rows, 1), jnp.float32),
            jnp.zeros((rows, dv), jnp.float32))


def _finish(o_ref, carry):
    _, l, acc = carry
    l = jnp.where(l == 0.0, 1.0, l)  # dead row -> zero output
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def _paged_bt_kernel(table_ref, len_ref, layer_ref, q_ref, *refs,
                     num_kv_heads: int, pages: int, block_size: int,
                     quant: bool, scale: float):
    # refs: the HBM pools (the k, v stacks[, k_scale, v_scale of the
    # layer]), the output, the VMEM chunk buffers (one per pool) and the DMA
    # semaphores; k and v are read at layer_ref[0] in place
    n_pools = 4 if quant else 2
    pools_hbm = [p.at[layer_ref[0]] for p in refs[:2]] + list(refs[2:n_pools])
    o_ref = refs[n_pools]
    bufs = refs[n_pools + 1:2 * n_pools + 1]
    sem = refs[2 * n_pools + 1]
    nkv = num_kv_heads
    chunk = pages * block_size
    b = pl.program_id(0)
    length = len_ref[b]

    # All query heads score the whole chunk at once: column c of the
    # (a, chunk * nkv) score tile is token c // nkv of kv head c % nkv, and
    # query head r keeps only the columns of its kv head r // g (the rest
    # get probability 0).  The chunk is read in its pool layout, with no
    # per-head strided read; the extra MXU work is nkv-fold on a tile the
    # MXU barely fills.
    q = q_ref[0]                                         # (a, d)
    a = q.shape[0]
    cols = (a, chunk * nkv)
    head_ok = (jax.lax.broadcasted_iota(jnp.int32, cols, 1) % nkv
               == jax.lax.broadcasted_iota(jnp.int32, cols, 0) // (a // nkv))
    col_tok = jax.lax.broadcasted_iota(jnp.int32, cols, 1) // nkv

    def scale_row(buf, slot):
        # a chunk's per-(token, head) scales as one (1, chunk * nkv) row,
        # in the score tile's column order
        return jnp.concatenate([buf[slot, p:p + 1, :] for p in range(pages)],
                               axis=1)

    def body(i, slot, carry):
        k = bufs[0][slot]                                # (chunk * nkv, d)
        v = bufs[1][slot].astype(jnp.float32)
        # q . k in the pair's common dtype (int8 keys as q's dtype, exact):
        # bf16 x bf16 products are exact in the f32 accumulator
        dt = q.dtype if quant else jnp.promote_types(q.dtype, k.dtype)
        s = jax.lax.dot_general(q.astype(dt), k.astype(dt),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if quant:
            s = s * scale_row(bufs[2], slot)
        s = jnp.where(head_ok & (col_tok < length - i * chunk), s, NEG_INF)
        return _online_softmax(s, carry, v,
                               scale_row(bufs[3], slot) if quant else None)

    _finish(o_ref, _live_chunks(
        table_ref, pools_hbm, bufs, sem, row=b, length=length, pages=pages,
        block_size=block_size, body=body, init=_softmax_init(a, q.shape[1])))


def paged_decode_blocktable_pallas(q: jax.Array, k_blocks: jax.Array,
                                   v_blocks: jax.Array,
                                   block_tables: jax.Array,
                                   lengths: jax.Array,
                                   layer: jax.Array | int = 0, *,
                                   k_scale: jax.Array | None = None,
                                   v_scale: jax.Array | None = None,
                                   block_kv: int | None = None,
                                   scale: float | None = None,
                                   interpret: bool = False) -> jax.Array:
    """q: (b, a, d) one token per row; k_blocks, v_blocks: (num_blocks,
    block_size, nkv, d) physical KV block pool, or (layers, num_blocks,
    block_size, nkv, d) with `layer` naming the one to read (the pool is
    read in place); block_tables: (b, max_blocks) int32 — row b's logical
    kv block j lives in physical block `block_tables[b, j]`; lengths: (b,)
    live kv per row.

    k_scale/v_scale: (num_blocks, block_size, nkv) f32 per-(token, kv_head)
    dequant scales of that one layer, for an int8 block pool (both or
    neither); their pages are copied beside the K/V pages and applied in
    VMEM.

    block_kv is the chunk length in tokens (default CHUNK_TOKENS), rounded
    up to whole pages (`blocktable_chunk_pages`).  Only the first
    ceil(lengths[b] / block_size) table entries of a row are read, so
    entries past them may hold any value.
    Returns (b, a, d); rows with length 0 return zeros.
    """
    from jax.experimental.pallas import tpu as pltpu
    b, a, d = q.shape
    if k_blocks.ndim == 4:
        k_blocks, v_blocks = k_blocks[None], v_blocks[None]
    n_layers, nb, block_size, nkv, dk = k_blocks.shape
    bt_rows, max_blocks = block_tables.shape
    assert d == dk and a % nkv == 0 and bt_rows == b
    assert (k_scale is None) == (v_scale is None)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    # a page as (block_size * nkv, d) rows: a bitcast of the pool wherever
    # nkv fills the sublane tile, as it does at the engine's widths
    pools = [k_blocks.reshape(n_layers, nb, block_size * nkv, d),
             v_blocks.reshape(n_layers, nb, block_size * nkv, d)]
    if k_scale is not None:
        assert k_scale.shape == (nb, block_size, nkv), k_scale.shape
        pools += [x.astype(jnp.float32).reshape(nb, block_size * nkv)
                  for x in (k_scale, v_scale)]
    page_bytes = block_size * nkv * sum(
        (d if x.ndim == 4 else 1) * x.dtype.itemsize for x in pools)
    pages = blocktable_chunk_pages(block_size, max_blocks, page_bytes,
                                   block_kv)
    rows = pages * block_size * nkv
    row_spec = pl.BlockSpec((1, a, d),
                            lambda bi, table, lens, layer: (bi, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    bufs = [pltpu.VMEM((2, rows, d), x.dtype) for x in pools[:2]]
    bufs += [pltpu.VMEM((2, pages, block_size * nkv), jnp.float32)
             for _ in pools[2:]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[row_spec] + [hbm] * len(pools),
        out_specs=row_spec,
        scratch_shapes=bufs + [pltpu.SemaphoreType.DMA((len(pools), 2))],
    )
    return pl.pallas_call(
        functools.partial(_paged_bt_kernel, num_kv_heads=nkv, pages=pages,
                          block_size=block_size, quant=k_scale is not None,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, a, d), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, *pools)


def _paged_mla_kernel(table_ref, len_ref, layer_ref, q_ref, pool_hbm, o_ref,
                      buf, sem, *, pages: int, block_size: int, v_dim: int,
                      scale: float):
    # every query head of the row against one latent "head": the page is
    # (block_size, c), c = kv_lora + rope padded to whole lanes; v is its
    # first v_dim columns
    chunk = pages * block_size
    b = pl.program_id(0)
    length = len_ref[b]
    q = q_ref[0]                                         # (a, c)
    a = q.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (a, chunk), 1)

    def body(i, slot, carry):
        kv = buf[slot]                                   # (chunk, c)
        s = jax.lax.dot_general(q, kv.astype(q.dtype),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(col < length - i * chunk, s, NEG_INF)
        return _online_softmax(s, carry, kv[:, :v_dim].astype(q.dtype))

    _finish(o_ref, _live_chunks(
        table_ref, (pool_hbm.at[layer_ref[0]],), (buf,), sem, row=b,
        length=length,
        pages=pages, block_size=block_size, body=body,
        init=_softmax_init(a, v_dim)))


def paged_mla_decode_pallas(q: jax.Array, latent_blocks: jax.Array,
                            block_tables: jax.Array, lengths: jax.Array,
                            layer: jax.Array | int = 0, *,
                            v_dim: int, scale: float,
                            block_kv: int | None = None,
                            interpret: bool = False) -> jax.Array:
    """MLA decode in the absorbed form over a latent block pool.

    q: (b, a, c) one token per row, each head's query already taken into
    the latent space (q_nope W_uk ++ q_rope ++ zeros); latent_blocks:
    (num_blocks, block_size, c) pages of normalised c_kv ++ roped k_rope ++
    zeros, c a multiple of 128 (a page is then whole lane tiles, which the
    page copies need), or (layers, num_blocks, block_size, c) with `layer`
    naming the one to read (the pool is read in place); block_tables: (b,
    max_blocks) int32; lengths: (b,).  Scores are q . latent for every head
    at once (one (a, chunk) tile), and the output is p . latent[:, :v_dim],
    still in the latent space (the caller applies W_uv).  The page loop is the GQA kernel's
    (`_live_chunks`): one grid step per row, its live pages only.
    Returns (b, a, v_dim); rows with length 0 return zeros.
    """
    from jax.experimental.pallas import tpu as pltpu
    b, a, c = q.shape
    if latent_blocks.ndim == 3:
        latent_blocks = latent_blocks[None]
    _, nb, block_size, ck = latent_blocks.shape
    bt_rows, max_blocks = block_tables.shape
    assert c == ck and bt_rows == b and v_dim <= c
    page_bytes = block_size * c * latent_blocks.dtype.itemsize
    pages = blocktable_chunk_pages(block_size, max_blocks, page_bytes,
                                   block_kv)
    row_spec = pl.BlockSpec((1, a, c),
                            lambda bi, table, lens, layer: (bi, 0, 0))
    out_spec = pl.BlockSpec((1, a, v_dim),
                            lambda bi, table, lens, layer: (bi, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[row_spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((2, pages * block_size, c), latent_blocks.dtype),
            pltpu.SemaphoreType.DMA((1, 2))],
    )
    return pl.pallas_call(
        functools.partial(_paged_mla_kernel, pages=pages,
                          block_size=block_size, v_dim=v_dim, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, a, v_dim), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, latent_blocks)
