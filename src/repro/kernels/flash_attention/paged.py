"""Paged/slotted decode-attention Pallas TPU kernel for the serving engine.

One query token per request row, K/V read from a fixed pool of cache slots
(`serving.engine.kv_pool.SlotPool`).  The slot mapping and per-slot lengths
ride in as scalar-prefetch operands (`pltpu.PrefetchScalarGridSpec`), so the
K/V BlockSpec index maps *gather by slot index*: row b's kv blocks come from
pool slot `slot_idx[b]` — the Pallas analogue of vLLM's paged attention at
page size = one whole slot.

Grid (b, kv_steps), kv innermost; each kv tile is (block_kv, nkv, d) — all
kv heads of block_kv positions, so the block's two minor dims are the pool's
own (nkv, d), the layout the TPU compiler accepts for a (…, nkv, d) pool (a
per-head (1, d) slice of those dims is refused).  The kernel walks the heads
of a tile in a static loop; VMEM scratch carries the online softmax state
(m, l, acc) per head across kv steps (TPU grids are sequential per core).
Per-slot lengths do double duty:
  * kv blocks entirely past `lengths[b]` are skipped via pl.when — a dead
    slot (length 0) costs zero FLOPs and writes zeros;
  * the tail block is masked elementwise so slot-pool positions past the
    sequence's live prefix (stale data from a previous occupant) never
    contribute.

The score tile is (g, block_kv) where g = query heads per kv head: decode
works at tiny sublane occupancy by construction (the paper's skinny-GEMM
regime); block_kv is the lane-side knob the autotuner sweeps
(`tuning.search.autotune_paged_decode`).

`paged_decode_blocktable_pallas` is the block-table variant (vLLM paged
attention at a real page size): K/V live in a pool of physical blocks of
`block_size` tokens and row b's logical kv block j comes from
`block_table[b, j]`.  The scalar-prefetch operands carry `(block_table[b, j],
lengths[b])`, so the BlockSpec index map gathers each kv tile from an
arbitrary physical block; the kernel body is shared with the slot variant
(it only sees logical kv positions).  Here *two* knobs are tile-lattice
choices the autotuner sweeps jointly: the physical block size (the paging
granule, a weight on copy/gather cost and sharing granularity) and block_kv
(the kv tile per grid step, dividing the block size) — see
`tuning.search.autotune_paged_decode_blocktable`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _paged_kernel(slot_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                  num_kv_heads: int, kv_steps: int, block_kv: int,
                  scale: float):
    # rest is (o, m, l, acc) for the plain variant, or
    # (ks, vs, o, m, l, acc) when the pool is int8-quantized KV: the scale
    # tiles ride as extra inputs and the dequant happens per kv tile, so the
    # pool stays 1 byte/elem in HBM and only live tiles pay the multiply.
    if len(rest) == 6:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    b_i, ki = pl.program_id(0), pl.program_id(1)
    length = len_ref[b_i]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip blocks wholly past the live prefix (dead slot: skips everything)
    @pl.when(ki * block_kv < length)
    def _step():
        kv_pos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_kv), 1)
        # one kv tile carries every kv head (the (nkv, d) minor dims are the
        # pool's own, which keeps the block legal on the TPU); each head's
        # (bkv, d) slab is a strided read of that tile
        for h in range(num_kv_heads):
            q = q_ref[0, h].astype(jnp.float32)          # (g, d)
            k = k_ref[0, :, h, :].astype(jnp.float32)    # (bkv, d)
            v = v_ref[0, :, h, :].astype(jnp.float32)    # (bkv, d)
            if ks_ref is not None:                       # per-(token, head)
                k = k * ks_ref[0, :, h:h + 1]
                v = v * vs_ref[0, :, h:h + 1]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            s = jnp.where(kv_pos < length, s, NEG_INF)
            m_prev = m_ref[h]                            # (g, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(ki == kv_steps - 1)
    def _done():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)  # dead slot -> zero output
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _scratch(nkv: int, g: int, d: int):
    """Online-softmax state (m, l, acc) for every kv head of one row."""
    from jax.experimental.pallas import tpu as pltpu
    return [pltpu.VMEM((nkv, g, 1), jnp.float32),
            pltpu.VMEM((nkv, g, 1), jnp.float32),
            pltpu.VMEM((nkv, g, d), jnp.float32)]


def paged_decode_pallas(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        slot_idx: jax.Array, lengths: jax.Array, *,
                        k_scale: jax.Array | None = None,
                        v_scale: jax.Array | None = None,
                        block_kv: int = 128, scale: float | None = None,
                        interpret: bool = False) -> jax.Array:
    """q: (b, a, d) one token per row; k_pool, v_pool: (slots, s_max, nkv, d);
    slot_idx: (b,) int32 row->slot; lengths: (b,) int32 live kv per row.

    k_scale/v_scale: (slots, s_max, nkv) f32 per-(token, kv_head) dequant
    scales for an int8 pool (both or neither); the kernel dequantizes each
    kv tile in VMEM, so HBM traffic stays at 1 byte per cached element.

    Requires s_max % block_kv == 0 (ops.py clamps/pads) and a % nkv == 0.
    Returns (b, a, d); rows with length 0 return zeros.
    """
    b, a, d = q.shape
    slots, s_max, nkv, dk = k_pool.shape
    assert d == dk and a % nkv == 0
    assert s_max % block_kv == 0, (s_max, block_kv)
    assert (k_scale is None) == (v_scale is None)
    g = a // nkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    kv_steps = s_max // block_kv
    qh = q.reshape(b, nkv, g, d)
    from jax.experimental.pallas import tpu as pltpu
    kv_spec = pl.BlockSpec((1, block_kv, nkv, d),
                           lambda bi, j, slot, lens: (slot[bi], j, 0, 0))
    row_spec = pl.BlockSpec((1, nkv, g, d),
                            lambda bi, j, slot, lens: (bi, 0, 0, 0))
    in_specs = [row_spec, kv_spec, kv_spec]
    operands = [qh, k_pool, v_pool]
    if k_scale is not None:
        assert k_scale.shape == (slots, s_max, nkv), k_scale.shape
        sc_spec = pl.BlockSpec((1, block_kv, nkv),
                               lambda bi, j, slot, lens: (slot[bi], j, 0))
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kv_steps),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=_scratch(nkv, g, d),
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, num_kv_heads=nkv, kv_steps=kv_steps,
                          block_kv=block_kv, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, g, d), q.dtype),
        interpret=interpret,
    )(slot_idx.astype(jnp.int32), lengths.astype(jnp.int32), *operands)
    return out.reshape(b, a, d)


def paged_decode_blocktable_pallas(q: jax.Array, k_blocks: jax.Array,
                                   v_blocks: jax.Array,
                                   block_tables: jax.Array,
                                   lengths: jax.Array, *,
                                   k_scale: jax.Array | None = None,
                                   v_scale: jax.Array | None = None,
                                   block_kv: int | None = None,
                                   scale: float | None = None,
                                   interpret: bool = False) -> jax.Array:
    """q: (b, a, d) one token per row; k_blocks, v_blocks: (num_blocks,
    block_size, nkv, d) physical KV block pool; block_tables: (b,
    max_blocks) int32 — row b's logical kv block j lives in physical block
    `block_tables[b, j]`; lengths: (b,) live kv per row.

    k_scale/v_scale: (num_blocks, block_size, nkv) f32 per-(token, kv_head)
    dequant scales for an int8 block pool (both or neither); tiles are
    dequantized in VMEM after the gather-by-table DMA.

    block_kv (default block_size) must divide block_size; the grid runs
    max_blocks * block_size/block_kv kv steps per row and skips
    steps wholly past `lengths[b]`, so table entries beyond a row's live
    blocks are never read (callers pad with any valid block id).
    Returns (b, a, d); rows with length 0 return zeros.
    """
    b, a, d = q.shape
    nb, block_size, nkv, dk = k_blocks.shape
    bt_rows, max_blocks = block_tables.shape
    assert d == dk and a % nkv == 0 and bt_rows == b
    assert (k_scale is None) == (v_scale is None)
    block_kv = block_kv or block_size
    assert block_size % block_kv == 0, (block_size, block_kv)
    g = a // nkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    steps_per_block = block_size // block_kv
    kv_steps = max_blocks * steps_per_block
    qh = q.reshape(b, nkv, g, d)
    from jax.experimental.pallas import tpu as pltpu

    def kv_spec():
        # logical kv step j -> (physical block, tile within block): the
        # scalar-prefetched table is indexed *inside the index map*, so the
        # DMA for row bi streams straight from the right physical block
        return pl.BlockSpec(
            (1, block_kv, nkv, d),
            lambda bi, j, table, lens: (table[bi, j // steps_per_block],
                                        j % steps_per_block, 0, 0))

    row_spec = pl.BlockSpec((1, nkv, g, d),
                            lambda bi, j, table, lens: (bi, 0, 0, 0))
    in_specs = [row_spec, kv_spec(), kv_spec()]
    operands = [qh, k_blocks, v_blocks]
    if k_scale is not None:
        assert k_scale.shape == (nb, block_size, nkv), k_scale.shape
        def sc_spec():
            return pl.BlockSpec(
                (1, block_kv, nkv),
                lambda bi, j, table, lens: (table[bi, j // steps_per_block],
                                            j % steps_per_block, 0))
        in_specs += [sc_spec(), sc_spec()]
        operands += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kv_steps),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=_scratch(nkv, g, d),
    )
    # the kernel body is the slot variant's: it reasons purely in logical kv
    # positions (ki * block_kv + offset vs lengths[b]); only the index maps
    # above know the physical indirection
    out = pl.pallas_call(
        functools.partial(_paged_kernel, num_kv_heads=nkv, kv_steps=kv_steps,
                          block_kv=block_kv, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, g, d), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), *operands)
    return out.reshape(b, a, d)
