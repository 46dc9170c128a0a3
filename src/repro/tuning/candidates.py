"""Tile-aligned block-size candidate lattices (the autotuner's search space).

The paper's thesis is that tile geometry drives efficiency; this module turns
that into a *search space*: every candidate block shape is (a) a multiple of
the hardware's native (sublane, lane) register tile at the given dtype, and
(b) small enough that the kernel's VMEM working set fits the chip's on-chip
memory budget (`Hardware.sram_bytes`).  The autotuner (`tuning.search`) then
*measures* each candidate instead of trusting the analytic model — closing
the loop between the roofline prediction and the kernel that actually runs.
"""
from __future__ import annotations

from typing import List, Tuple

from ..core.hardware import Hardware, get_hardware
from ..core.quantization import round_up

# Don't let the lattice explode: per-dimension caps keep the sweep tractable
# while covering every block size the kernels plausibly benefit from.
MAX_BLOCK = 1024
# Double-buffering factor for streamed input blocks (Pallas pipelines the
# next block's DMA while computing on the current one).
DOUBLE_BUFFER = 2


def sublane_granule(hw: Hardware, dtype_bytes: int = 2) -> int:
    """Native second-to-minor tile granularity at `dtype_bytes`.

    TPU packs (32 / dtype_bytes) x 128 register tiles (f32: 8, bf16: 16,
    int8: 32) — the same scaling quantization.tile_utilization applies.
    """
    sub, _ = hw.tile_2byte
    if hw.name.startswith("tpu"):
        return max(1, sub * 2 // max(dtype_bytes, 1))
    return sub


def lane_granule(hw: Hardware) -> int:
    """Minor-most tile granularity (always the full lane width)."""
    return hw.tile_2byte[1]


def _steps(dim: int, granule: int, cap: int = MAX_BLOCK) -> List[int]:
    """Power-of-two multiples of `granule`, capped by the (padded) problem
    dim and `cap` — blocks larger than the problem only add padding."""
    hi = min(cap, round_up(max(dim, 1), granule))
    out = []
    b = granule
    while b <= hi:
        out.append(b)
        b *= 2
    if not out:
        out = [granule]
    return out


def bucket_steps(dim: int, granule: int, cap: int = MAX_BLOCK) -> List[int]:
    """Public lattice for the serving engine's bucket policy: power-of-two
    multiples of the hardware tile granule, up to (the padding of) `dim`.

    The engine snaps batch/prompt buckets to this lattice so every lowered
    program shape is tile-aligned and the jit program set is bounded —
    the same lattice the autotuner sweeps (`_steps`)."""
    return _steps(dim, granule, cap)


def matmul_vmem_bytes(block_m: int, block_n: int, block_k: int,
                      dtype_bytes: int = 2) -> int:
    """VMEM working set of kernels/matmul: double-buffered A and B input
    blocks, an f32 accumulator scratch, and the output block."""
    a_blk = block_m * block_k * dtype_bytes
    b_blk = block_k * block_n * dtype_bytes
    acc = block_m * block_n * 4
    out = block_m * block_n * dtype_bytes
    return DOUBLE_BUFFER * (a_blk + b_blk) + acc + out


def flash_vmem_bytes(block_q: int, block_kv: int, head_dim: int,
                     dtype_bytes: int = 2) -> int:
    """VMEM working set of kernels/flash_attention: q block + double-buffered
    k/v blocks + (m, l, acc) f32 scratch + the f32 score tile + output."""
    q_blk = block_q * head_dim * dtype_bytes
    kv_blk = 2 * block_kv * head_dim * dtype_bytes
    scratch = block_q * head_dim * 4 + 2 * block_q * 4
    scores = block_q * block_kv * 4
    out = block_q * head_dim * dtype_bytes
    return q_blk + DOUBLE_BUFFER * kv_blk + scratch + scores + out


def flash_bwd_vmem_bytes(block_q: int, block_kv: int, head_dim: int,
                         dtype_bytes: int = 2) -> int:
    """VMEM working set of the flash backward kernels (the dkv grid is the
    larger of the two): streamed q/do blocks + lse/di rows, resident k/v
    blocks, the f32 score and ds tiles, two f32 (block_kv, d) accumulators,
    and the dk/dv output blocks."""
    q_stream = DOUBLE_BUFFER * (2 * block_q * head_dim * dtype_bytes
                                + 2 * block_q * 4)
    kv_blk = 2 * block_kv * head_dim * dtype_bytes
    tiles = 2 * block_q * block_kv * 4
    acc = 2 * block_kv * head_dim * 4
    out = 2 * block_kv * head_dim * dtype_bytes
    return q_stream + kv_blk + tiles + acc + out


def flash_backward_candidates(seq_q: int, seq_kv: int, head_dim: int,
                              hw: Hardware | None = None,
                              dtype_bytes: int = 2,
                              max_candidates: int | None = None
                              ) -> List[Tuple[int, int]]:
    """All (block_q, block_kv) worth timing for the flash *backward* grids.

    Same tile-alignment lattice as `flash_candidates`, but under the
    backward VMEM model: the dkv kernel keeps two extra f32 accumulators and
    the ds tile resident, so the feasible region is strictly smaller than
    the forward's.  The 128x128 default is always included.
    """
    return _flash_lattice(seq_q, seq_kv, head_dim, flash_bwd_vmem_bytes,
                          hw, dtype_bytes, max_candidates)


def _gemm_lattice(m: int, n: int, k: int, vmem_bytes,
                  hw: Hardware | None, dtype_bytes: int,
                  max_candidates: int | None) -> List[Tuple[int, int, int]]:
    """Shared (block_m, block_n, block_k) lattice for the GEMM-shaped sweeps
    (matmul and the fused MLP hidden): block_m sublane-aligned, block_n and
    block_k lane-aligned, feasibility decided by the given VMEM model.
    Candidates are ordered largest-first (bigger blocks amortize more grid
    overhead and are usually the winners on real hardware) and the 128^3
    default is always included when it fits — it is the baseline the
    measured speedup is quoted against."""
    hw = hw or get_hardware()
    sub = sublane_granule(hw, dtype_bytes)
    lane = lane_granule(hw)
    # block_m starts at the MXU row count if the problem allows: sub-MXU
    # blocks only make sense for skinny problems.
    m_steps = [s for s in _steps(m, sub) if s >= min(128, round_up(m, sub))]
    m_steps = m_steps or _steps(m, sub)[-1:]
    n_steps = _steps(n, lane)
    k_steps = _steps(k, lane)
    cands = [
        (bm, bn, bk)
        for bm in m_steps
        for bn in n_steps
        for bk in k_steps
        if vmem_bytes(bm, bn, bk) <= hw.sram_bytes
    ]
    cands.sort(key=lambda c: -(c[0] * c[1] * c[2]))
    default = (128, 128, 128)
    if default not in cands and vmem_bytes(*default) <= hw.sram_bytes:
        cands.append(default)
    if max_candidates is not None and len(cands) > max_candidates:
        keep = cands[:max_candidates]
        if default in cands and default not in keep:
            keep[-1] = default
        cands = keep
    return cands


def matmul_candidates(m: int, k: int, n: int, hw: Hardware | None = None,
                      dtype_bytes: int = 2,
                      max_candidates: int | None = None
                      ) -> List[Tuple[int, int, int]]:
    """All (block_m, block_n, block_k) worth timing for an (m, k, n) GEMM.

    Every candidate is tile-aligned (block_m % sublane == 0, block_n and
    block_k % lane == 0) and fits the VMEM budget (`_gemm_lattice`).
    """
    return _gemm_lattice(
        m, n, k,
        lambda bm, bn, bk: matmul_vmem_bytes(bm, bn, bk, dtype_bytes),
        hw, dtype_bytes, max_candidates)


def fused_mlp_vmem_bytes(block_m: int, block_f: int, block_k: int,
                         dtype_bytes: int = 2, gated: bool = True) -> int:
    """VMEM working set of kernels/fused_mlp forward: double-buffered x and
    gate/up weight blocks, one f32 accumulator per GEMM of the pair, and the
    combined hidden output block.  The gated (swiglu) variant streams two
    weight blocks and keeps two accumulators resident — its feasible region
    is strictly smaller than a plain matmul's at equal blocks."""
    nw = 2 if gated else 1
    x_blk = block_m * block_k * dtype_bytes
    w_blk = nw * block_k * block_f * dtype_bytes
    acc = nw * block_m * block_f * 4
    out = block_m * block_f * dtype_bytes
    return DOUBLE_BUFFER * (x_blk + w_blk) + acc + out


def fused_mlp_candidates(m: int, h: int, f: int, hw: Hardware | None = None,
                         dtype_bytes: int = 2, gated: bool = True,
                         max_candidates: int | None = None
                         ) -> List[Tuple[int, int, int]]:
    """All (block_m, block_f, block_k) worth timing for an (m, h, f) fused
    MLP hidden problem (m tokens, h model width, f ffn width).

    Same tile-alignment lattice as `matmul_candidates` (block_m sublane-,
    block_f/block_k lane-aligned) under the fused-MLP VMEM model; the 128^3
    default is always included.  The §VII-B hook: an 8h/3 heuristic f pads
    up to the lattice and the waste shows up in every candidate's timing.
    """
    return _gemm_lattice(
        m, f, h,
        lambda bm, bn, bk: fused_mlp_vmem_bytes(bm, bn, bk, dtype_bytes, gated),
        hw, dtype_bytes, max_candidates)


def int8_matmul_vmem_bytes(block_m: int, block_n: int, block_k: int) -> int:
    """VMEM working set of kernels/quantized int8 matmul: double-buffered
    int8 A and B blocks (1 byte/elem), the i32 accumulator scratch, the f32
    scale rows/cols, and the output block (f32 worst case).  Halving operand
    bytes roughly doubles the feasible block area vs the bf16 lattice —
    the dtype/shape coupling the paper's alignment rules predict."""
    a_blk = block_m * block_k * 1
    b_blk = block_k * block_n * 1
    scales = (block_m + block_n) * 4
    acc = block_m * block_n * 4
    out = block_m * block_n * 4
    return DOUBLE_BUFFER * (a_blk + b_blk + scales) + acc + out


def int8_matmul_candidates(m: int, k: int, n: int, hw: Hardware | None = None,
                           max_candidates: int | None = None
                           ) -> List[Tuple[int, int, int]]:
    """All (block_m, block_n, block_k) worth timing for an int8 (m, k, n)
    GEMM.  The lattice quantizes block_m to the *int8* sublane granule
    (32 on TPU — four int8 rows pack per register row) under the int8 VMEM
    model; the 128^3 default is always included."""
    return _gemm_lattice(
        m, n, k,
        lambda bm, bn, bk: int8_matmul_vmem_bytes(bm, bn, bk),
        hw, 1, max_candidates)


def fp8_matmul_vmem_bytes(block_m: int, block_n: int, block_k: int) -> int:
    """VMEM working set of the emulated-fp8 matmul.  The GEMM itself runs
    the bf16-path kernel on widened operands, so the resident footprint is
    the 2-byte matmul model — fp8 only changes the HBM story."""
    return matmul_vmem_bytes(block_m, block_n, block_k, 2)


def fp8_matmul_candidates(m: int, k: int, n: int, hw: Hardware | None = None,
                          max_candidates: int | None = None
                          ) -> List[Tuple[int, int, int]]:
    """(block_m, block_n, block_k) lattice for the emulated-fp8 GEMM: bf16
    tile granules (the compute path is the bf16 MXU) bounded by
    `fp8_matmul_vmem_bytes`."""
    return _gemm_lattice(
        m, n, k,
        lambda bm, bn, bk: fp8_matmul_vmem_bytes(bm, bn, bk),
        hw, 2, max_candidates)


def int8_fused_mlp_vmem_bytes(block_m: int, block_f: int, block_k: int,
                              gated: bool = True) -> int:
    """VMEM working set of the int8-weight fused MLP: double-buffered int8 x
    and weight blocks plus f32 scale vectors, one i32 accumulator per GEMM
    of the pair, and the f32 hidden output block."""
    nw = 2 if gated else 1
    x_blk = block_m * block_k * 1
    w_blk = nw * block_k * block_f * 1
    scales = (block_m + nw * block_f) * 4
    acc = nw * block_m * block_f * 4
    out = block_m * block_f * 4
    return DOUBLE_BUFFER * (x_blk + w_blk + scales) + acc + out


def int8_fused_mlp_candidates(m: int, h: int, f: int,
                              hw: Hardware | None = None, gated: bool = True,
                              max_candidates: int | None = None
                              ) -> List[Tuple[int, int, int]]:
    """(block_m, block_f, block_k) lattice for the int8 fused-MLP hidden:
    int8 sublane granule on block_m, bounded by `int8_fused_mlp_vmem_bytes`
    (two i32 accumulators for the gated pair); 128^3 always included."""
    return _gemm_lattice(
        m, f, h,
        lambda bm, bn, bk: int8_fused_mlp_vmem_bytes(bm, bn, bk, gated),
        hw, 1, max_candidates)


def paged_blocktable_candidates(seq_max: int, head_dim: int, group: int = 1,
                                hw: Hardware | None = None,
                                dtype_bytes: int = 2,
                                max_candidates: int | None = None
                                ) -> List[Tuple[int, int]]:
    """(block_size, block_kv) pairs worth timing for the block-table decode
    kernel — the paging granule and the chunk are swept *jointly*.

    block_size candidates are power-of-two multiples of the sublane granule
    that divide seq_max (so a full sequence is a whole number of blocks and
    the pool capacity proof num_blocks = rows * seq_max/block_size holds);
    block_kv is the kernel's chunk in tokens, a power-of-two number of
    whole blocks up to seq_max, within the streaming VMEM budget at
    block_q = group.  Larger pairs first: longer chunks amortise each loop
    step, but small blocks buy sharing granularity — that tension is
    exactly what the measurement decides.
    """
    hw = hw or get_hardware()
    sub = sublane_granule(hw, dtype_bytes)
    sizes = [bs for bs in _steps(seq_max, sub, cap=min(MAX_BLOCK, seq_max))
             if seq_max % bs == 0]
    cands = [
        (bs, bkv)
        for bs in sizes
        for bkv in _steps(seq_max, bs, cap=min(MAX_BLOCK, seq_max))
        if flash_vmem_bytes(group, bkv, head_dim, dtype_bytes)
        <= hw.sram_bytes
    ]
    cands.sort(key=lambda c: (-c[0], -c[1]))
    if max_candidates is not None and len(cands) > max_candidates:
        # keep coverage across block sizes rather than the head of the list
        # (which is all-largest-block): take the biggest bkv per size first
        by_size: List[Tuple[int, int]] = []
        seen = set()
        for bs, bkv in cands:
            if bs not in seen:
                by_size.append((bs, bkv))
                seen.add(bs)
        rest = [c for c in cands if c not in by_size]
        cands = (by_size + rest)[:max_candidates]
        cands.sort(key=lambda c: (-c[0], -c[1]))
    return cands


def _flash_lattice(seq_q: int, seq_kv: int, head_dim: int, vmem_bytes,
                   hw: Hardware | None, dtype_bytes: int,
                   max_candidates: int | None) -> List[Tuple[int, int]]:
    """Shared (block_q, block_kv) lattice for the flash forward/backward
    sweeps: block_q sublane-aligned, block_kv lane-aligned (the score tile
    feeds the MXU), feasibility decided by the given VMEM model.  The
    128x128 default is always included when it fits."""
    hw = hw or get_hardware()
    sub = sublane_granule(hw, dtype_bytes)
    lane = lane_granule(hw)
    q_steps = [s for s in _steps(seq_q, sub) if s >= min(128, round_up(seq_q, sub))]
    q_steps = q_steps or _steps(seq_q, sub)[-1:]
    kv_steps = _steps(seq_kv, lane)
    cands = [
        (bq, bkv)
        for bq in q_steps
        for bkv in kv_steps
        if vmem_bytes(bq, bkv, head_dim, dtype_bytes) <= hw.sram_bytes
    ]
    cands.sort(key=lambda c: -(c[0] * c[1]))
    default = (128, 128)
    if default not in cands and vmem_bytes(*default, head_dim, dtype_bytes) <= hw.sram_bytes:
        cands.append(default)
    if max_candidates is not None and len(cands) > max_candidates:
        keep = cands[:max_candidates]
        if default in cands and default not in keep:
            keep[-1] = default
        cands = keep
    return cands


def flash_candidates(seq_q: int, seq_kv: int, head_dim: int,
                     hw: Hardware | None = None, dtype_bytes: int = 2,
                     max_candidates: int | None = None
                     ) -> List[Tuple[int, int]]:
    """All (block_q, block_kv) worth timing for a flash-attention problem.

    block_q is sublane-aligned, block_kv lane-aligned (the (block_q,
    block_kv) score tile feeds the MXU), and the streaming working set must
    fit VMEM.  The 128x128 default is always included.
    """
    return _flash_lattice(seq_q, seq_kv, head_dim, flash_vmem_bytes,
                          hw, dtype_bytes, max_candidates)
