"""jit'd public wrapper for the tile-aligned GEMM kernel.

`matmul` pads misaligned problems up to the block grid (tile quantization
made explicit — the zero-padding FLOPs are exactly the waste the paper's
utilization term predicts) and reports alignment via `alignment_report`.

With `tuned=True` the wrapper consults the autotuning cache
(`repro.tuning.cache`) for a measured-best block shape for this exact
(m, k, n, dtype, hardware) before falling back to the 128^3 default —
see `repro.tuning.search.autotune_matmul` for how entries are produced.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ... import obs
from ...core.hardware import get_hardware
from ...core.quantization import round_up, tile_utilization
from ...tuning.cache import lookup as _tuning_lookup
from ..backend import interpret_mode
from .kernel import matmul_pallas
from .ref import matmul_ref


def _pad2(x, m, n):
    pm, pn = m - x.shape[0], n - x.shape[1]
    if pm == 0 and pn == 0:
        return x
    return jnp.pad(x, ((0, pm), (0, pn)))


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret", "use_pallas"))
def _matmul_jit(a: jax.Array, b: jax.Array, *,
                block_m: int, block_n: int, block_k: int,
                interpret: bool, use_pallas: bool) -> jax.Array:
    if not use_pallas:
        return matmul_ref(a, b)
    m, k = a.shape
    _, n = b.shape
    mp, kp, np_ = round_up(m, block_m), round_up(k, block_k), round_up(n, block_n)
    out = matmul_pallas(_pad2(a, mp, kp), _pad2(b, kp, np_),
                        block_m=block_m, block_n=block_n, block_k=block_k,
                        interpret=interpret)
    return out[:m, :n]


def matmul(a: jax.Array, b: jax.Array, *,
           block_m: int = 128, block_n: int = 128, block_k: int = 128,
           interpret: Optional[bool] = None, use_pallas: bool = True,
           tuned: bool = False, hw_name: Optional[str] = None) -> jax.Array:
    """C = A @ B.  A: (..., k) — leading dims are flattened into one m axis
    and restored on the output, so a (b, s, h) activation keys the tuning
    cache as (b*s, h, n), the exact shape `autotune_matmul` writes (a
    >2-D A used to miss the cache silently).  use_pallas=False falls back
    to the jnp oracle.  interpret=None compiles the kernel on a TPU and
    interprets it on any other backend (`kernels.backend.interpret_mode`).

    tuned=True overrides block_* with the autotuning cache's measured-best
    config for this (m, k, n, dtype, hw) when one exists (cache misses keep
    the defaults).  The lookup runs at trace time, outside the jit.
    """
    lead = a.shape[:-1]
    if a.ndim != 2:
        a = a.reshape(-1, a.shape[-1])
    tuned_hit = None
    if tuned and use_pallas:
        m, k = a.shape
        _, n = b.shape
        cfg = _tuning_lookup("matmul", (m, k, n), jnp.dtype(a.dtype).name,
                             hw_name or get_hardware().name)
        tuned_hit = cfg is not None
        if cfg is not None:
            block_m = cfg.blocks["block_m"]
            block_n = cfg.blocks["block_n"]
            block_k = cfg.blocks["block_k"]
    if obs.enabled():
        obs.record_dispatch(
            "matmul", impl="pallas" if use_pallas else "jnp",
            shape=(a.shape[0], a.shape[1], b.shape[-1]),
            blocks={"block_m": block_m, "block_n": block_n,
                    "block_k": block_k} if use_pallas else None,
            tuned_hit=tuned_hit)
    out = _matmul_jit(a, b, block_m=block_m, block_n=block_n,
                      block_k=block_k, interpret=interpret_mode(interpret),
                      use_pallas=use_pallas)
    return out if len(lead) == 1 else out.reshape(*lead, b.shape[-1])


def alignment_report(m: int, k: int, n: int, dtype=jnp.bfloat16,
                     hw_name: Optional[str] = None) -> dict:
    """Tile-alignment report for an (m, k, n) GEMM.  `dtype` (an array dtype,
    not a byte count) and `hw_name` default to the benchmark dtype and
    `get_hardware()`'s default chip; callers on other hardware thread their
    own through."""
    from ...core.gemm_model import GEMM, recommend_precision
    hw = get_hardware(hw_name) if hw_name else get_hardware()
    dtype_bytes = jnp.dtype(dtype).itemsize
    util = tile_utilization(m, n, k, hw, dtype_bytes)
    gemm = GEMM("alignment_report", m, k, n, dtype_bytes=dtype_bytes)
    rec_dtype, rec_speedup = recommend_precision(
        gemm, hw, dtypes=(jnp.dtype(dtype).name, "int8"))
    return {
        "hw_name": hw.name,
        "dtype": jnp.dtype(dtype).name,
        "mxu_utilization": util,
        "padded_shape": (round_up(m, 128), round_up(k, 128), round_up(n, 128)),
        "aligned": util > 0.999,
        "vmem_per_tile_bytes": (128 * 128 * dtype_bytes * 2 + 128 * 128 * 4),
        # dtype-aware pricing: int8 weights win exactly where the GEMM is
        # bandwidth-bound (see core.gemm_model.recommend_precision)
        "int8_utilization": tile_utilization(m, n, k, hw, 1),
        "recommended_dtype": rec_dtype,
        "recommended_speedup": rec_speedup,
    }
