"""Numeric quantization helpers: int8 and emulated-fp8 value compression.

Naming note — this package is about *numeric* quantization (compressing
tensor values to fewer bits); `repro.core.quantization` is about *tile/wave*
quantization (utilization loss from shape-vs-hardware-tile mismatch, paper
§III-B/§VI-B).  The two concepts share a name in the literature but nothing
else; keep imports explicit to avoid collisions.

Conventions (match the Pallas int8 idiom):

  * symmetric absmax scaling: ``scale = max|x| / 127``, ``q = round(x/scale)``
    clipped to [-127, 127] (−128 unused so the range is symmetric),
  * scales are float32 and live *alongside* the int8 payload — weights carry
    one scale per output channel, activations one per row, KV-cache entries
    one per (token, kv_head),
  * fp8 is *emulated*: values are rounded through ``float8_e4m3fn`` /
    ``float8_e5m2`` storage and widened back, so the matmul itself runs on
    the bf16 MXU path.  This reproduces fp8 numerics (and HBM bytes, when
    stored) without requiring fp8 matmul units.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

INT8_MAX = 127.0
# emulated fp8 storage formats (both 1 byte; e4m3 = more mantissa,
# e5m2 = more range)
FP8_DTYPES = ("float8_e4m3fn", "float8_e5m2")
# smallest scale we divide by; absmax-zero slices quantize to all-zeros
EPS = 1e-8


class QuantizedTensor(NamedTuple):
    """An int8 payload plus the float32 scales that de-quantize it.

    ``axis`` is the *contraction-reduced* axis the scales were computed over
    (scales have that axis collapsed to size 1), kept so ``dequantize``
    can broadcast without re-deriving it.
    """

    q: jax.Array       # int8 values
    scale: jax.Array   # float32, broadcastable against q
    axis: int          # axis reduced when computing absmax


def quantize_int8(x, axis: int = -1) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-slice int8 quantization.

    Returns ``(q, scale)`` with ``q`` int8 and ``scale`` float32 shaped like
    ``x`` with ``axis`` collapsed to 1, such that ``q * scale ~= x``.
    """
    x = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(absmax, EPS) / INT8_MAX
    q = jnp.clip(jnp.round(x / scale), -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale, dtype=jnp.float32):
    """Inverse of `quantize_int8`: widen and re-scale."""
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


def quantize_weight(w, dtype: str = "int8") -> QuantizedTensor:
    """Quantize a (k, n) weight matrix per *output channel* (reduce over k).

    Per-channel scales are the standard accuracy/throughput sweet spot for
    weight-only int8: each output column sees its own dynamic range, and the
    de-scale folds into the GEMM epilogue as a (1, n) row-vector multiply.
    """
    if dtype == "int8":
        q, scale = quantize_int8(w, axis=-2)
        return QuantizedTensor(q=q, scale=scale, axis=-2)
    if dtype in FP8_DTYPES:
        # fp8 emulation keeps a trivial all-ones scale: the rounding itself
        # is the compression, the storage dtype carries the exponent
        q = w.astype(jnp.dtype(dtype))
        scale = jnp.ones((1,) * w.ndim, jnp.float32)
        return QuantizedTensor(q=q, scale=scale, axis=-2)
    raise ValueError(
        f"unknown quant dtype {dtype!r}; valid: ['int8', *{list(FP8_DTYPES)}]")


def fp8_round_trip(x, fp8_dtype: str = "float8_e4m3fn"):
    """Round `x` through fp8 storage and widen back to its input dtype.

    This is the emulation primitive: the value grid (and therefore the
    numerics) are fp8's, while the compute that follows stays on the bf16 /
    f32 MXU path.
    """
    if fp8_dtype not in FP8_DTYPES:
        raise ValueError(
            f"unknown fp8 dtype {fp8_dtype!r}; valid: {list(FP8_DTYPES)}")
    return x.astype(jnp.dtype(fp8_dtype)).astype(x.dtype)


# -- KV-cache quantization -----------------------------------------------------
def quantize_kv(x) -> Tuple[jax.Array, jax.Array]:
    """Quantize a KV tensor (..., kv_heads, head_dim) per (token, kv_head).

    Returns (int8 values, float32 scales with head_dim dropped) — the layout
    the quantized PagedPool leaves carry ("k"/"v" int8 plus
    "k_scale"/"v_scale" float32, see models/blocks._kv_cache_shape).
    """
    q, scale = quantize_int8(x, axis=-1)
    return q, scale[..., 0]


def dequantize_kv(q, scale, dtype=jnp.bfloat16):
    """Inverse of `quantize_kv`: scales broadcast back over head_dim."""
    return dequantize_int8(q, scale[..., None], dtype)


def kv_bytes_per_token(num_kv_heads: int, head_dim: int,
                       kv_dtype: str = "auto",
                       compute_bytes: int = 2) -> int:
    """Per-token-per-layer KV bytes (K and V), the slots-per-GiB numerator.

    int8 stores 1 byte per element plus one f32 scale per (token, head) for
    each of K and V; "auto" stores the compute dtype.
    """
    elems = 2 * num_kv_heads * head_dim  # K and V
    if kv_dtype == "int8":
        return elems * 1 + 2 * num_kv_heads * 4
    return elems * compute_bytes
