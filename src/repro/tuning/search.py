"""Measured block-size search for the Pallas kernels.

For each problem shape the search sweeps the tile-aligned candidate lattice
(`tuning.candidates`), times every candidate with `tuning.measure.wall_us`,
and records the winner in a `TuningCache` — the measured counterpart of the
analytic model in `core.gemm_model`.  Kernel wrappers then consult the cache
via `tuned=True`, and `core.gemm_model.MeasuredProfile` uses the same
entries to calibrate advisor predictions.

Every search takes `interpret=None` by default, which follows the backend
(`kernels.backend.interpret_mode`): on a TPU the candidates run compiled and
the cache holds hardware timings; on the CPU they run in Pallas interpret
mode, whose absolute times are not TPU times, though the full loop (search
-> cache -> tuned dispatch -> calibrated advisor) is exercised end to end.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import obs
from ..core.hardware import Hardware, get_hardware
from .cache import TunedConfig, TuningCache, get_default_cache, mixed_dtype
from .candidates import (flash_backward_candidates, flash_candidates,
                         fp8_matmul_candidates, fused_mlp_candidates,
                         int8_fused_mlp_candidates, int8_matmul_candidates,
                         matmul_candidates, paged_blocktable_candidates,
                         paged_decode_candidates)
from .measure import wall_us

DEFAULT_MATMUL_BLOCKS = (128, 128, 128)
DEFAULT_FLASH_BLOCKS = (128, 128)
DEFAULT_PAGED_BLOCK_KV = 128
DEFAULT_FUSED_MLP_BLOCKS = (128, 128, 128)


@dataclasses.dataclass(frozen=True)
class Trial:
    blocks: Tuple[int, ...]
    time_us: float
    time_us_std: float = 0.0


def _measure(op: str, fn, *args, iters: int, warmup: int,
             jit: bool = False) -> Tuple[float, float]:
    """Time one candidate with per-iteration samples: (mean_us, std_us).

    The std rides into `Trial`/`TunedConfig.time_us_std` so a winner whose
    margin over the runner-up is inside the noise band is visible as such;
    with obs enabled the raw samples also feed a per-op histogram."""
    mean, samples = wall_us(fn, *args, iters=iters, warmup=warmup, jit=jit,
                            return_samples=True)
    std = statistics.pstdev(samples) if len(samples) > 1 else 0.0
    if obs.enabled():
        obs.histogram(f"tuning.{op}.us").observe_many(samples)
    return mean, std


def flash_op_name(causal: bool) -> str:
    return "flash_attention_causal" if causal else "flash_attention_full"


def flash_bwd_op_name(causal: bool) -> str:
    return ("flash_attention_bwd_causal" if causal
            else "flash_attention_bwd_full")


def _dtype_name(dtype) -> str:
    return jnp.dtype(dtype).name


def autotune_matmul(m: int, k: int, n: int, *, dtype=jnp.float32,
                    hw: Optional[Hardware] = None,
                    cache: Optional[TuningCache] = None,
                    interpret: Optional[bool] = None, iters: int = 3, warmup: int = 1,
                    max_candidates: Optional[int] = None,
                    verbose: bool = False) -> TunedConfig:
    """Sweep (block_m, block_n, block_k) for an (m, k, n) matmul; persist
    and return the measured winner.  `cache=None` uses the default cache."""
    from ..kernels.matmul.ops import matmul

    hw = hw or get_hardware()
    cache = cache if cache is not None else get_default_cache()
    dtype_bytes = jnp.dtype(dtype).itemsize
    cands = matmul_candidates(m, k, n, hw, dtype_bytes,
                              max_candidates=max_candidates)
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (m, k)).astype(dtype)
    b = jax.random.normal(jax.random.fold_in(key, 1), (k, n)).astype(dtype)

    trials: List[Trial] = []
    baseline_us = 0.0
    for bm, bn, bk in cands:
        t, std = _measure(
            "matmul",
            lambda a, b, bm=bm, bn=bn, bk=bk: matmul(
                a, b, block_m=bm, block_n=bn, block_k=bk,
                interpret=interpret),
            a, b, iters=iters, warmup=warmup)
        trials.append(Trial((bm, bn, bk), t, std))
        if (bm, bn, bk) == DEFAULT_MATMUL_BLOCKS:
            baseline_us = t
        if verbose:
            print(f"  matmul {m}x{k}x{n} blocks=({bm},{bn},{bk}): {t:.1f} us")
    best = min(trials, key=lambda t: t.time_us)
    cfg = TunedConfig(
        op="matmul", shape=(m, k, n), dtype=_dtype_name(dtype),
        hw_name=hw.name,
        blocks={"block_m": best.blocks[0], "block_n": best.blocks[1],
                "block_k": best.blocks[2]},
        time_us=best.time_us, baseline_us=baseline_us,
        candidates_tried=len(trials), time_us_std=best.time_us_std)
    cache.put(cfg)
    return cfg


def autotune_fused_mlp(m: int, h: int, f: int, *, mlp_type: str = "swiglu",
                       dtype=jnp.float32, hw: Optional[Hardware] = None,
                       cache: Optional[TuningCache] = None,
                       interpret: Optional[bool] = None, iters: int = 3,
                       warmup: int = 1,
                       max_candidates: Optional[int] = None,
                       verbose: bool = False) -> TunedConfig:
    """Sweep (block_m, block_f, block_k) for an (m, h, f) fused MLP hidden
    problem (kernels/fused_mlp); persist and return the measured winner
    under op "fused_mlp_<mlp_type>".

    `fused_mlp_hidden(tuned=True)` — and therefore `linear_impl="fused"`
    model MLPs, which flatten (b, s, h) to m = b*s — picks the entry up by
    the same (m, h, f) key.
    """
    from ..kernels.fused_mlp.ops import fused_mlp_hidden, fused_mlp_op_name
    from ..kernels.fused_mlp.ref import is_gated

    hw = hw or get_hardware()
    cache = cache if cache is not None else get_default_cache()
    dtype_bytes = jnp.dtype(dtype).itemsize
    gated = is_gated(mlp_type)
    cands = fused_mlp_candidates(m, h, f, hw, dtype_bytes, gated=gated,
                                 max_candidates=max_candidates)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (m, h)).astype(dtype)
    wg = (jax.random.normal(jax.random.fold_in(key, 1), (h, f)).astype(dtype)
          if gated else None)
    wu = jax.random.normal(jax.random.fold_in(key, 2), (h, f)).astype(dtype)

    trials: List[Trial] = []
    baseline_us = 0.0
    for bm, bf, bk in cands:
        t, std = _measure(
            fused_mlp_op_name(mlp_type),
            lambda x, wu, bm=bm, bf=bf, bk=bk: fused_mlp_hidden(
                x, wg, wu, mlp_type=mlp_type, block_m=bm, block_f=bf,
                block_k=bk, interpret=interpret),
            x, wu, iters=iters, warmup=warmup)
        trials.append(Trial((bm, bf, bk), t, std))
        if (bm, bf, bk) == DEFAULT_FUSED_MLP_BLOCKS:
            baseline_us = t
        if verbose:
            print(f"  fused_mlp[{mlp_type}] {m}x{h}x{f} "
                  f"blocks=({bm},{bf},{bk}): {t:.1f} us")
    best = min(trials, key=lambda t: t.time_us)
    cfg = TunedConfig(
        op=fused_mlp_op_name(mlp_type), shape=(m, h, f),
        dtype=_dtype_name(dtype), hw_name=hw.name,
        blocks={"block_m": best.blocks[0], "block_f": best.blocks[1],
                "block_k": best.blocks[2]},
        time_us=best.time_us, baseline_us=baseline_us,
        candidates_tried=len(trials), time_us_std=best.time_us_std)
    cache.put(cfg)
    return cfg


def autotune_int8_matmul(m: int, k: int, n: int, *, dtype=jnp.float32,
                         hw: Optional[Hardware] = None,
                         cache: Optional[TuningCache] = None,
                         interpret: Optional[bool] = None, iters: int = 3,
                         warmup: int = 1,
                         max_candidates: Optional[int] = None,
                         verbose: bool = False) -> TunedConfig:
    """Sweep (block_m, block_n, block_k) for an int8-weight (m, k, n) GEMM
    over the int8 lattice (32-sublane granule, int8 VMEM model); persist and
    return the winner under op "int8_matmul" with the *mixed* dtype key
    (activation x weight, e.g. "float32xint8") — the key
    `int8_matmul(tuned=True)` looks up."""
    from ..kernels.quantized.ops import int8_matmul
    from ..quant import quantize_weight

    hw = hw or get_hardware()
    cache = cache if cache is not None else get_default_cache()
    cands = int8_matmul_candidates(m, k, n, hw, max_candidates=max_candidates)
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (m, k)).astype(dtype)
    wq = quantize_weight(
        jax.random.normal(jax.random.fold_in(key, 1), (k, n)).astype(dtype))

    trials: List[Trial] = []
    baseline_us = 0.0
    for bm, bn, bk in cands:
        t, std = _measure(
            "int8_matmul",
            lambda a, bm=bm, bn=bn, bk=bk: int8_matmul(
                a, wq, block_m=bm, block_n=bn, block_k=bk,
                interpret=interpret),
            a, iters=iters, warmup=warmup)
        trials.append(Trial((bm, bn, bk), t, std))
        if (bm, bn, bk) == DEFAULT_MATMUL_BLOCKS:
            baseline_us = t
        if verbose:
            print(f"  int8_matmul {m}x{k}x{n} blocks=({bm},{bn},{bk}): "
                  f"{t:.1f} us")
    best = min(trials, key=lambda t: t.time_us)
    cfg = TunedConfig(
        op="int8_matmul", shape=(m, k, n),
        dtype=mixed_dtype(_dtype_name(dtype), "int8"), hw_name=hw.name,
        blocks={"block_m": best.blocks[0], "block_n": best.blocks[1],
                "block_k": best.blocks[2]},
        time_us=best.time_us, baseline_us=baseline_us,
        candidates_tried=len(trials), time_us_std=best.time_us_std)
    cache.put(cfg)
    return cfg


def autotune_fp8_matmul(m: int, k: int, n: int, *,
                        fp8_dtype: str = "float8_e4m3fn", dtype=jnp.float32,
                        hw: Optional[Hardware] = None,
                        cache: Optional[TuningCache] = None,
                        interpret: Optional[bool] = None, iters: int = 3,
                        warmup: int = 1,
                        max_candidates: Optional[int] = None,
                        verbose: bool = False) -> TunedConfig:
    """Sweep blocks for the emulated-fp8 (m, k, n) GEMM; persist the winner
    under op "fp8_matmul" with the mixed dtype key (e.g.
    "float32xfloat8_e4m3fn")."""
    from ..kernels.quantized.ops import fp8_matmul

    hw = hw or get_hardware()
    cache = cache if cache is not None else get_default_cache()
    cands = fp8_matmul_candidates(m, k, n, hw, max_candidates=max_candidates)
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (m, k)).astype(dtype)
    b = jax.random.normal(jax.random.fold_in(key, 1), (k, n)).astype(dtype)

    trials: List[Trial] = []
    baseline_us = 0.0
    for bm, bn, bk in cands:
        t, std = _measure(
            "fp8_matmul",
            lambda a, b, bm=bm, bn=bn, bk=bk: fp8_matmul(
                a, b, fp8_dtype=fp8_dtype, block_m=bm, block_n=bn,
                block_k=bk, interpret=interpret),
            a, b, iters=iters, warmup=warmup)
        trials.append(Trial((bm, bn, bk), t, std))
        if (bm, bn, bk) == DEFAULT_MATMUL_BLOCKS:
            baseline_us = t
        if verbose:
            print(f"  fp8_matmul[{fp8_dtype}] {m}x{k}x{n} "
                  f"blocks=({bm},{bn},{bk}): {t:.1f} us")
    best = min(trials, key=lambda t: t.time_us)
    cfg = TunedConfig(
        op="fp8_matmul", shape=(m, k, n),
        dtype=mixed_dtype(_dtype_name(dtype), fp8_dtype), hw_name=hw.name,
        blocks={"block_m": best.blocks[0], "block_n": best.blocks[1],
                "block_k": best.blocks[2]},
        time_us=best.time_us, baseline_us=baseline_us,
        candidates_tried=len(trials), time_us_std=best.time_us_std)
    cache.put(cfg)
    return cfg


def autotune_int8_fused_mlp(m: int, h: int, f: int, *,
                            mlp_type: str = "swiglu", dtype=jnp.float32,
                            hw: Optional[Hardware] = None,
                            cache: Optional[TuningCache] = None,
                            interpret: Optional[bool] = None, iters: int = 3,
                            warmup: int = 1,
                            max_candidates: Optional[int] = None,
                            verbose: bool = False) -> TunedConfig:
    """Sweep (block_m, block_f, block_k) for the int8-weight fused-MLP
    hidden; persist the winner under op "int8_fused_mlp_<mlp_type>" with the
    mixed dtype key."""
    from ..kernels.fused_mlp.ref import is_gated
    from ..kernels.quantized.ops import (int8_fused_mlp_hidden,
                                         int8_fused_mlp_op_name)
    from ..quant import quantize_weight

    hw = hw or get_hardware()
    cache = cache if cache is not None else get_default_cache()
    gated = is_gated(mlp_type)
    cands = int8_fused_mlp_candidates(m, h, f, hw, gated=gated,
                                      max_candidates=max_candidates)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (m, h)).astype(dtype)
    wg = (quantize_weight(jax.random.normal(
        jax.random.fold_in(key, 1), (h, f)).astype(dtype)) if gated else None)
    wu = quantize_weight(jax.random.normal(
        jax.random.fold_in(key, 2), (h, f)).astype(dtype))

    trials: List[Trial] = []
    baseline_us = 0.0
    for bm, bf, bk in cands:
        t, std = _measure(
            int8_fused_mlp_op_name(mlp_type),
            lambda x, bm=bm, bf=bf, bk=bk: int8_fused_mlp_hidden(
                x, wg, wu, mlp_type=mlp_type, block_m=bm, block_f=bf,
                block_k=bk, interpret=interpret),
            x, iters=iters, warmup=warmup)
        trials.append(Trial((bm, bf, bk), t, std))
        if (bm, bf, bk) == DEFAULT_FUSED_MLP_BLOCKS:
            baseline_us = t
        if verbose:
            print(f"  int8_fused_mlp[{mlp_type}] {m}x{h}x{f} "
                  f"blocks=({bm},{bf},{bk}): {t:.1f} us")
    best = min(trials, key=lambda t: t.time_us)
    cfg = TunedConfig(
        op=int8_fused_mlp_op_name(mlp_type), shape=(m, h, f),
        dtype=mixed_dtype(_dtype_name(dtype), "int8"), hw_name=hw.name,
        blocks={"block_m": best.blocks[0], "block_f": best.blocks[1],
                "block_k": best.blocks[2]},
        time_us=best.time_us, baseline_us=baseline_us,
        candidates_tried=len(trials), time_us_std=best.time_us_std)
    cache.put(cfg)
    return cfg


def autotune_paged_decode(batch: int, slots: int, s_max: int, kv_heads: int,
                          heads: int, head_dim: int, *, dtype=jnp.float32,
                          hw: Optional[Hardware] = None,
                          cache: Optional[TuningCache] = None,
                          interpret: Optional[bool] = None, iters: int = 3,
                          warmup: int = 1,
                          max_candidates: Optional[int] = None,
                          verbose: bool = False) -> TunedConfig:
    """Sweep block_kv for the serving engine's paged decode kernel over a
    (slots, s_max, kv_heads, head_dim) KV pool with `batch` active rows;
    persist and return the measured winner (op "paged_decode")."""
    from ..kernels.flash_attention.ops import paged_decode

    hw = hw or get_hardware()
    cache = cache if cache is not None else get_default_cache()
    dtype_bytes = jnp.dtype(dtype).itemsize
    g = heads // kv_heads
    cands = paged_decode_candidates(s_max, head_dim, g, hw, dtype_bytes,
                                    max_candidates=max_candidates)
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (batch, heads, head_dim)).astype(dtype)
    pool_shape = (slots, s_max, kv_heads, head_dim)
    kp = jax.random.normal(jax.random.fold_in(key, 1), pool_shape).astype(dtype)
    vp = jax.random.normal(jax.random.fold_in(key, 2), pool_shape).astype(dtype)
    slot_idx = jnp.arange(batch, dtype=jnp.int32) % slots
    lengths = jnp.full((batch,), s_max, jnp.int32)

    trials: List[Trial] = []
    baseline_us = 0.0
    for bkv in cands:
        t, std = _measure(
            "paged_decode",
            lambda q, kp, vp, si, ln, bkv=bkv: paged_decode(
                q, kp, vp, si, ln, block_kv=bkv, interpret=interpret),
            q, kp, vp, slot_idx, lengths, iters=iters, warmup=warmup)
        trials.append(Trial((bkv,), t, std))
        if bkv == DEFAULT_PAGED_BLOCK_KV:
            baseline_us = t
        if verbose:
            print(f"  paged b{batch} pool{slots}x{s_max} kv{kv_heads} "
                  f"d{head_dim} block_kv={bkv}: {t:.1f} us")
    best = min(trials, key=lambda t: t.time_us)
    cfg = TunedConfig(
        op="paged_decode",
        shape=(batch, slots, s_max, kv_heads, heads, head_dim),
        dtype=_dtype_name(dtype), hw_name=hw.name,
        blocks={"block_kv": best.blocks[0]},
        time_us=best.time_us, baseline_us=baseline_us,
        candidates_tried=len(trials), time_us_std=best.time_us_std)
    cache.put(cfg)
    return cfg


def autotune_paged_decode_blocktable(batch: int, num_rows: int, s_max: int,
                                     kv_heads: int, heads: int,
                                     head_dim: int, *, dtype=jnp.float32,
                                     hw: Optional[Hardware] = None,
                                     cache: Optional[TuningCache] = None,
                                     interpret: Optional[bool] = None, iters: int = 3,
                                     warmup: int = 1,
                                     max_candidates: Optional[int] = None,
                                     verbose: bool = False) -> TunedConfig:
    """Jointly sweep (block_size, block_kv) for the block-table decode kernel
    over a pool sized for `num_rows` sequences of up to `s_max` tokens.

    Each block_size candidate implies its own pool geometry — num_blocks =
    num_rows * s_max/block_size physical blocks of block_size tokens — so the
    paging granule is measured as a real cost (more table indirections per
    row at small blocks vs. coarser sharing at large ones), not assumed.

    Two kinds of cache entry are written:
      * op "paged_decode_blocktable_pool", shape (batch, num_rows, s_max,
        kv_heads, heads, head_dim), blocks {block_size, block_kv} — the
        engine-level entry `ServeEngine(prefix_cache=True)` consults to pick
        its physical block size;
      * op "paged_decode_blocktable", shape (batch, num_blocks, block_size,
        kv_heads, heads, head_dim), blocks {block_kv} — one per block_size
        tried (best block_kv at that size), so
        `paged_decode_blocktable(tuned=True)` hits whatever pool shape the
        engine ends up running.
    Returns the pool-level winner.
    """
    from ..kernels.flash_attention.ops import paged_decode_blocktable

    hw = hw or get_hardware()
    cache = cache if cache is not None else get_default_cache()
    dtype_bytes = jnp.dtype(dtype).itemsize
    g = heads // kv_heads
    cands = paged_blocktable_candidates(s_max, head_dim, g, hw, dtype_bytes,
                                        max_candidates=max_candidates)
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (batch, heads, head_dim)).astype(dtype)
    lengths = jnp.full((batch,), s_max, jnp.int32)

    trials: List[Trial] = []
    best_at_size: dict = {}
    for bs, bkv in cands:
        max_blocks = s_max // bs
        nb = num_rows * max_blocks
        pool_shape = (nb, bs, kv_heads, head_dim)
        kb = jax.random.normal(jax.random.fold_in(key, 1),
                               pool_shape).astype(dtype)
        vb = jax.random.normal(jax.random.fold_in(key, 2),
                               pool_shape).astype(dtype)
        tables = (jnp.arange(batch, dtype=jnp.int32)[:, None] * max_blocks
                  + jnp.arange(max_blocks, dtype=jnp.int32)[None, :]) % nb
        t, std = _measure(
            "paged_decode_blocktable",
            lambda q, kb, vb, tb, ln, bs=bs, bkv=bkv: paged_decode_blocktable(
                q, kb, vb, tb, ln, block_kv=bkv, interpret=interpret),
            q, kb, vb, tables, lengths, iters=iters, warmup=warmup)
        trials.append(Trial((bs, bkv), t, std))
        if bs not in best_at_size or t < best_at_size[bs][1]:
            best_at_size[bs] = (bkv, t, nb, std)
        if verbose:
            print(f"  paged_bt b{batch} rows{num_rows} s{s_max} kv{kv_heads} "
                  f"d{head_dim} block_size={bs} block_kv={bkv}: {t:.1f} us")
    # per-pool-shape entries: the kernel-level tuned lookup
    for bs, (bkv, t, nb, std) in best_at_size.items():
        cache.put(TunedConfig(
            op="paged_decode_blocktable",
            shape=(batch, nb, bs, kv_heads, heads, head_dim),
            dtype=_dtype_name(dtype), hw_name=hw.name,
            blocks={"block_kv": bkv}, time_us=t, baseline_us=0.0,
            candidates_tried=sum(1 for tr in trials if tr.blocks[0] == bs),
            time_us_std=std))
    best = min(trials, key=lambda t: t.time_us)
    # baseline for the speedup quote: the coarsest paging granule tried
    # (one block = whole sequence, i.e. the slot-pool layout)
    bs_max = max(bs for bs, _ in cands)
    baseline_us = min((t.time_us for t in trials if t.blocks[0] == bs_max),
                      default=0.0)
    cfg = TunedConfig(
        op="paged_decode_blocktable_pool",
        shape=(batch, num_rows, s_max, kv_heads, heads, head_dim),
        dtype=_dtype_name(dtype), hw_name=hw.name,
        blocks={"block_size": best.blocks[0], "block_kv": best.blocks[1]},
        time_us=best.time_us, baseline_us=baseline_us,
        candidates_tried=len(trials), time_us_std=best.time_us_std)
    cache.put(cfg)
    return cfg


def autotune_flash_attention(batch: int, seq: int, heads: int, head_dim: int,
                             *, seq_kv: Optional[int] = None,
                             causal: bool = True, dtype=jnp.float32,
                             hw: Optional[Hardware] = None,
                             cache: Optional[TuningCache] = None,
                             interpret: Optional[bool] = None, iters: int = 3,
                             warmup: int = 1,
                             max_candidates: Optional[int] = None,
                             verbose: bool = False) -> TunedConfig:
    """Sweep (block_q, block_kv) for a (batch, seq, heads, head_dim)
    attention problem; persist and return the measured winner."""
    from ..kernels.flash_attention.ops import flash_attention

    hw = hw or get_hardware()
    cache = cache if cache is not None else get_default_cache()
    seq_kv = seq_kv or seq
    dtype_bytes = jnp.dtype(dtype).itemsize
    cands = flash_candidates(seq, seq_kv, head_dim, hw, dtype_bytes,
                             max_candidates=max_candidates)
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (batch, seq, heads, head_dim)).astype(dtype)
    kv_shape = (batch, seq_kv, heads, head_dim)
    k = jax.random.normal(jax.random.fold_in(key, 1), kv_shape).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), kv_shape).astype(dtype)

    trials: List[Trial] = []
    baseline_us = 0.0
    for bq, bkv in cands:
        t, std = _measure(
            flash_op_name(causal),
            lambda q, k, v, bq=bq, bkv=bkv: flash_attention(
                q, k, v, causal=causal, block_q=bq, block_kv=bkv,
                interpret=interpret),
            q, k, v, iters=iters, warmup=warmup)
        trials.append(Trial((bq, bkv), t, std))
        if (bq, bkv) == DEFAULT_FLASH_BLOCKS:
            baseline_us = t
        if verbose:
            print(f"  flash b{batch} s{seq} a{heads} d{head_dim} "
                  f"blocks=({bq},{bkv}): {t:.1f} us")
    best = min(trials, key=lambda t: t.time_us)
    cfg = TunedConfig(
        op=flash_op_name(causal),
        shape=(batch, seq, seq_kv, heads, head_dim),
        dtype=_dtype_name(dtype), hw_name=hw.name,
        blocks={"block_q": best.blocks[0], "block_kv": best.blocks[1]},
        time_us=best.time_us, baseline_us=baseline_us,
        candidates_tried=len(trials), time_us_std=best.time_us_std)
    cache.put(cfg)
    return cfg


def autotune_flash_backward(batch: int, seq: int, heads: int, head_dim: int,
                            *, seq_kv: Optional[int] = None,
                            causal: bool = True, dtype=jnp.float32,
                            hw: Optional[Hardware] = None,
                            cache: Optional[TuningCache] = None,
                            interpret: Optional[bool] = None, iters: int = 3,
                            warmup: int = 1,
                            max_candidates: Optional[int] = None,
                            verbose: bool = False) -> TunedConfig:
    """Sweep (block_q, block_kv) for the flash-attention *backward* grids of
    a (batch, seq, heads, head_dim) problem; persist and return the measured
    winner under op "flash_attention_bwd_causal" / "..._full".

    Each trial times jax.grad through `flash_attention` with the forward
    pinned to its 128 defaults and only the backward blocks varying, so the
    ranking isolates the dq/dkv grids (the forward cost is a constant
    offset).  `flash_attention(tuned=True)` then picks the entry up
    alongside the forward one — forward and backward tile geometries tune
    independently, as on real hardware.
    """
    from ..kernels.flash_attention.ops import flash_attention

    hw = hw or get_hardware()
    cache = cache if cache is not None else get_default_cache()
    seq_kv = seq_kv or seq
    dtype_bytes = jnp.dtype(dtype).itemsize
    cands = flash_backward_candidates(seq, seq_kv, head_dim, hw, dtype_bytes,
                                      max_candidates=max_candidates)
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (batch, seq, heads, head_dim)).astype(dtype)
    kv_shape = (batch, seq_kv, heads, head_dim)
    k = jax.random.normal(jax.random.fold_in(key, 1), kv_shape).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), kv_shape).astype(dtype)

    trials: List[Trial] = []
    baseline_us = 0.0
    for bq, bkv in cands:
        def vjp(q, k, v, bq=bq, bkv=bkv):
            return jax.grad(
                lambda q, k, v: flash_attention(
                    q, k, v, causal=causal, bwd_block_q=bq, bwd_block_kv=bkv,
                    interpret=interpret).sum().astype(jnp.float32),
                argnums=(0, 1, 2))(q, k, v)
        t, std = _measure(flash_bwd_op_name(causal), vjp, q, k, v,
                          iters=iters, warmup=warmup, jit=True)
        trials.append(Trial((bq, bkv), t, std))
        if (bq, bkv) == DEFAULT_FLASH_BLOCKS:
            baseline_us = t
        if verbose:
            print(f"  flash_bwd b{batch} s{seq} a{heads} d{head_dim} "
                  f"blocks=({bq},{bkv}): {t:.1f} us")
    best = min(trials, key=lambda t: t.time_us)
    cfg = TunedConfig(
        op=flash_bwd_op_name(causal),
        shape=(batch, seq, seq_kv, heads, head_dim),
        dtype=_dtype_name(dtype), hw_name=hw.name,
        blocks={"block_q": best.blocks[0], "block_kv": best.blocks[1]},
        time_us=best.time_us, baseline_us=baseline_us,
        candidates_tried=len(trials), time_us_std=best.time_us_std)
    cache.put(cfg)
    return cfg
