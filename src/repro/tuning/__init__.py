"""Kernel autotuning: measured block-size search + persistent tuning cache.

The subsystem closes the loop the analytic model leaves open:

    candidates.py  tile-aligned (block_*) lattice under the VMEM budget
    measure.py     the wall-clock timer (shared with benchmarks/)
    search.py      sweep + time candidates, persist winners
    cache.py       JSON cache keyed by (op, shape, dtype, hw_name)

Kernel wrappers (`kernels/*/ops.py`) consult the default cache when called
with `tuned=True`; `core.gemm_model.MeasuredProfile` turns the same cache
into a calibration layer for `core.advisor` predictions.

`search` is imported lazily (PEP 562) because it imports the kernel
wrappers, which themselves import `tuning.cache` — eager import would cycle.
"""
from .cache import (TunedConfig, TuningCache, cache_key, default_cache_path,
                    get_default_cache, lookup, mixed_dtype, set_default_cache)
from .candidates import (bucket_steps, flash_backward_candidates,
                         flash_bwd_vmem_bytes, flash_candidates,
                         flash_vmem_bytes, fp8_matmul_candidates,
                         fp8_matmul_vmem_bytes, fused_mlp_candidates,
                         fused_mlp_vmem_bytes, int8_fused_mlp_candidates,
                         int8_fused_mlp_vmem_bytes, int8_matmul_candidates,
                         int8_matmul_vmem_bytes, matmul_candidates,
                         matmul_vmem_bytes, paged_blocktable_candidates)
from .measure import wall_us

_SEARCH_EXPORTS = ("autotune_matmul", "autotune_flash_attention",
                   "autotune_flash_backward", "autotune_fused_mlp",
                   "autotune_int8_matmul", "autotune_fp8_matmul",
                   "autotune_int8_fused_mlp",
                   "autotune_paged_decode_blocktable",
                   "flash_op_name", "flash_bwd_op_name")

__all__ = [
    "TunedConfig", "TuningCache", "cache_key", "default_cache_path",
    "get_default_cache", "lookup", "mixed_dtype", "set_default_cache",
    "bucket_steps", "flash_backward_candidates", "flash_bwd_vmem_bytes",
    "flash_candidates", "flash_vmem_bytes",
    "fp8_matmul_candidates", "fp8_matmul_vmem_bytes",
    "fused_mlp_candidates", "fused_mlp_vmem_bytes",
    "int8_fused_mlp_candidates", "int8_fused_mlp_vmem_bytes",
    "int8_matmul_candidates", "int8_matmul_vmem_bytes",
    "matmul_candidates", "matmul_vmem_bytes", "paged_blocktable_candidates",
    "wall_us", *_SEARCH_EXPORTS,
]


def __getattr__(name):
    if name in _SEARCH_EXPORTS:
        from . import search
        return getattr(search, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
