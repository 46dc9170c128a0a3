"""jit'd public wrappers for the low-precision GEMM kernels.

Mirrors kernels/matmul/ops.py: pad misaligned problems up to the block grid,
slice the result, consult the autotuning cache when `tuned=True`.  The cache
dtype key is the *mixed* key (`tuning.cache.mixed_dtype`) — e.g.
``bfloat16xint8`` — because the activation and weight dtypes differ and an
int8-weight entry must never shadow a uniform-dtype entry for the same
(m, k, n).

Quantization policy:
  * weights quantize per output channel, once — pass a
    `repro.quant.QuantizedTensor` (from `quantize_weight`) to amortize, or a
    float matrix to quantize on the fly;
  * activations quantize per row *inside* the jit (dynamic quantization) —
    the absmax reduce fuses with the surrounding program;
  * fp8 is emulated: operands round-trip through fp8 storage and the GEMM
    itself runs the bf16-path `matmul_pallas` kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ... import obs
from ...core.hardware import get_hardware
from ...core.quantization import round_up
from ...quant import QuantizedTensor, fp8_round_trip, quantize_int8, quantize_weight
from ...tuning.cache import lookup as _tuning_lookup
from ...tuning.cache import mixed_dtype
from ..backend import interpret_mode
from ..fused_mlp.ref import is_gated
from ..matmul.kernel import matmul_pallas
from ..matmul.ops import _pad2
from ..matmul.ref import matmul_ref
from .kernel import int8_fused_mlp_pallas, int8_matmul_pallas
from .ref import int8_fused_mlp_ref, int8_matmul_ref


def int8_fused_mlp_op_name(mlp_type: str) -> str:
    """Tuning-cache op key for the int8 fused-MLP hidden kernel."""
    return f"int8_fused_mlp_{mlp_type}"


def _as_quantized(w, name: str = "weight") -> QuantizedTensor:
    """Normalize a weight operand: pass through a prequantized container,
    quantize a float matrix per output channel on the fly."""
    if isinstance(w, QuantizedTensor):
        return w
    if w.dtype == jnp.int8:
        raise ValueError(
            f"{name}: raw int8 arrays are ambiguous — wrap the payload and "
            f"its scales in repro.quant.QuantizedTensor")
    return quantize_weight(w, "int8")


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "block_k", "interpret", "use_pallas", "out_dtype"))
def _int8_matmul_jit(a, b_q, b_scale, *, block_m: int, block_n: int,
                     block_k: int, interpret: bool, use_pallas: bool,
                     out_dtype: str):
    a_q, a_scale = quantize_int8(a, axis=-1)
    if not use_pallas:
        return int8_matmul_ref(a_q, a_scale, b_q, b_scale, jnp.dtype(out_dtype))
    m, k = a_q.shape
    _, n = b_q.shape
    mp, kp, np_ = round_up(m, block_m), round_up(k, block_k), round_up(n, block_n)
    out = int8_matmul_pallas(
        _pad2(a_q, mp, kp), _pad2(b_q, kp, np_),
        _pad2(a_scale, mp, 1), _pad2(b_scale, 1, np_),
        block_m=block_m, block_n=block_n, block_k=block_k,
        out_dtype=jnp.dtype(out_dtype), interpret=interpret)
    return out[:m, :n]


def int8_matmul(a: jax.Array, w, *,
                block_m: int = 128, block_n: int = 128, block_k: int = 128,
                interpret: Optional[bool] = None, use_pallas: bool = True,
                tuned: bool = False, hw_name: Optional[str] = None,
                out_dtype=None) -> jax.Array:
    """C = dequant(quant(A) @ quant(W)).  A: (..., k) float; W: (k, n) float
    or a prequantized `QuantizedTensor`.  Leading dims of A flatten to one m
    axis (same cache-key discipline as ops.matmul).

    tuned=True consults the cache under op "int8_matmul" with the mixed
    dtype key (activation x weight), so int8 tiles tune independently of the
    bf16 tiles for the same shape.
    """
    lead = a.shape[:-1]
    if a.ndim != 2:
        a = a.reshape(-1, a.shape[-1])
    wq = _as_quantized(w)
    b_q, b_scale = wq.q, wq.scale.reshape(1, -1)
    out_dtype = jnp.dtype(out_dtype or a.dtype).name
    tuned_hit = None
    if tuned and use_pallas:
        m, k = a.shape
        _, n = b_q.shape
        cfg = _tuning_lookup(
            "int8_matmul", (m, k, n),
            mixed_dtype(jnp.dtype(a.dtype).name, "int8"),
            hw_name or get_hardware().name)
        tuned_hit = cfg is not None
        if cfg is not None:
            block_m = cfg.blocks["block_m"]
            block_n = cfg.blocks["block_n"]
            block_k = cfg.blocks["block_k"]
    if obs.enabled():
        obs.record_dispatch(
            "int8_matmul", impl="pallas" if use_pallas else "jnp",
            shape=(a.shape[0], a.shape[1], b_q.shape[-1]),
            blocks={"block_m": block_m, "block_n": block_n,
                    "block_k": block_k} if use_pallas else None,
            tuned_hit=tuned_hit)
    out = _int8_matmul_jit(a, b_q, b_scale, block_m=block_m, block_n=block_n,
                           block_k=block_k, interpret=interpret_mode(interpret),
                           use_pallas=use_pallas, out_dtype=out_dtype)
    return out if len(lead) == 1 else out.reshape(*lead, b_q.shape[-1])


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "block_k", "interpret", "use_pallas", "fp8_dtype"))
def _fp8_matmul_jit(a, b, *, block_m: int, block_n: int, block_k: int,
                    interpret: bool, use_pallas: bool, fp8_dtype: str):
    a8 = fp8_round_trip(a, fp8_dtype)
    b8 = fp8_round_trip(b, fp8_dtype)
    if not use_pallas:
        return matmul_ref(a8, b8)
    m, k = a8.shape
    _, n = b8.shape
    mp, kp, np_ = round_up(m, block_m), round_up(k, block_k), round_up(n, block_n)
    out = matmul_pallas(_pad2(a8, mp, kp), _pad2(b8, kp, np_),
                        block_m=block_m, block_n=block_n, block_k=block_k,
                        interpret=interpret)
    return out[:m, :n]


def fp8_matmul(a: jax.Array, b: jax.Array, *,
               fp8_dtype: str = "float8_e4m3fn",
               block_m: int = 128, block_n: int = 128, block_k: int = 128,
               interpret: Optional[bool] = None, use_pallas: bool = True,
               tuned: bool = False, hw_name: Optional[str] = None) -> jax.Array:
    """Emulated-fp8 GEMM: round A and B through fp8 storage (e4m3 or e5m2),
    contract on the bf16-MXU-path kernel.  Cache op "fp8_matmul", mixed
    dtype key e.g. ``bfloat16xfloat8_e4m3fn``."""
    lead = a.shape[:-1]
    if a.ndim != 2:
        a = a.reshape(-1, a.shape[-1])
    tuned_hit = None
    if tuned and use_pallas:
        m, k = a.shape
        _, n = b.shape
        cfg = _tuning_lookup(
            "fp8_matmul", (m, k, n),
            mixed_dtype(jnp.dtype(a.dtype).name, fp8_dtype),
            hw_name or get_hardware().name)
        tuned_hit = cfg is not None
        if cfg is not None:
            block_m = cfg.blocks["block_m"]
            block_n = cfg.blocks["block_n"]
            block_k = cfg.blocks["block_k"]
    if obs.enabled():
        obs.record_dispatch(
            "fp8_matmul", impl="pallas" if use_pallas else "jnp",
            shape=(a.shape[0], a.shape[1], b.shape[-1]),
            blocks={"block_m": block_m, "block_n": block_n,
                    "block_k": block_k} if use_pallas else None,
            tuned_hit=tuned_hit)
    out = _fp8_matmul_jit(a, b, block_m=block_m, block_n=block_n,
                          block_k=block_k, interpret=interpret_mode(interpret),
                          use_pallas=use_pallas, fp8_dtype=fp8_dtype)
    return out if len(lead) == 1 else out.reshape(*lead, b.shape[-1])


@functools.partial(jax.jit, static_argnames=(
    "mlp_type", "block_m", "block_f", "block_k", "interpret", "use_pallas",
    "out_dtype"))
def _int8_fused_mlp_jit(x, wg_q, wg_scale, wu_q, wu_scale, *, mlp_type: str,
                        block_m: int, block_f: int, block_k: int,
                        interpret: bool, use_pallas: bool, out_dtype: str):
    x_q, x_scale = quantize_int8(x, axis=-1)
    if not use_pallas:
        return int8_fused_mlp_ref(x_q, x_scale, wg_q, wg_scale, wu_q, wu_scale,
                                  mlp_type=mlp_type,
                                  out_dtype=jnp.dtype(out_dtype))
    m, h = x_q.shape
    _, f = wu_q.shape
    mp, hp, fp = round_up(m, block_m), round_up(h, block_k), round_up(f, block_f)
    gated = is_gated(mlp_type)
    out = int8_fused_mlp_pallas(
        _pad2(x_q, mp, hp),
        _pad2(wg_q, hp, fp) if gated else None,
        _pad2(wu_q, hp, fp),
        _pad2(x_scale, mp, 1),
        _pad2(wg_scale, 1, fp) if gated else None,
        _pad2(wu_scale, 1, fp),
        mlp_type=mlp_type, block_m=block_m, block_f=block_f, block_k=block_k,
        out_dtype=jnp.dtype(out_dtype), interpret=interpret)
    return out[:m, :f]


def int8_fused_mlp_hidden(x: jax.Array, w_gate, w_up, *,
                          mlp_type: str = "swiglu",
                          block_m: int = 128, block_f: int = 128,
                          block_k: int = 128,
                          interpret: Optional[bool] = None,
                          use_pallas: bool = True, tuned: bool = False,
                          hw_name: Optional[str] = None,
                          out_dtype=None) -> jax.Array:
    """int8-weight fused-MLP hidden.  x: (..., h) float; w_gate/w_up: (h, f)
    float or prequantized `QuantizedTensor` (w_gate=None for ungated
    mlp_types).  Cache op ``int8_fused_mlp_<mlp_type>``, shape (m, h, f),
    mixed dtype key."""
    lead = x.shape[:-1]
    if x.ndim != 2:
        x = x.reshape(-1, x.shape[-1])
    gated = is_gated(mlp_type)
    wuq = _as_quantized(w_up, "w_up")
    wu_q, wu_scale = wuq.q, wuq.scale.reshape(1, -1)
    if gated:
        wgq = _as_quantized(w_gate, "w_gate")
        wg_q, wg_scale = wgq.q, wgq.scale.reshape(1, -1)
    else:
        wg_q = wg_scale = None
    out_dtype = jnp.dtype(out_dtype or x.dtype).name
    op = int8_fused_mlp_op_name(mlp_type)
    tuned_hit = None
    if tuned and use_pallas:
        m, h = x.shape
        _, f = wu_q.shape
        cfg = _tuning_lookup(op, (m, h, f),
                             mixed_dtype(jnp.dtype(x.dtype).name, "int8"),
                             hw_name or get_hardware().name)
        tuned_hit = cfg is not None
        if cfg is not None:
            block_m = cfg.blocks["block_m"]
            block_f = cfg.blocks["block_f"]
            block_k = cfg.blocks["block_k"]
    if obs.enabled():
        obs.record_dispatch(
            op, impl="pallas" if use_pallas else "jnp",
            shape=(x.shape[0], x.shape[1], wu_q.shape[-1]),
            blocks={"block_m": block_m, "block_f": block_f,
                    "block_k": block_k} if use_pallas else None,
            tuned_hit=tuned_hit)
    out = _int8_fused_mlp_jit(x, wg_q, wg_scale, wu_q, wu_scale,
                              mlp_type=mlp_type, block_m=block_m,
                              block_f=block_f, block_k=block_k,
                              interpret=interpret_mode(interpret),
                              use_pallas=use_pallas, out_dtype=out_dtype)
    return out if len(lead) == 1 else out.reshape(*lead, wu_q.shape[-1])
