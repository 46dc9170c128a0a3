"""Unified linear-execution layer: every model GEMM routes through here.

The paper's thesis is that transformer throughput is decided by the shapes
of a handful of GEMMs; this module is the single chokepoint where those
GEMMs actually execute, so tile-quantization waste is paid (and measured) in
one place.  `linear` flattens (b, s, h) activations to 2-D — producing the
exact (m, k, n) key the autotuner writes — and selects the execution path
from `ModelConfig.linear_impl` (mirroring `attn_impl`):

  "jnp"    — XLA `x @ w` (CPU/dry-run default; identical to the pre-refactor
             inline GEMMs, including gradients)
  "pallas" — the tile-aligned Pallas matmul kernel at its 128^3 defaults
  "tuned"  — Pallas + per-(m, k, n, dtype, hw) autotuning-cache blocks
  "fused"  — tuned dispatch everywhere, plus the fused SwiGLU/MLP Pallas
             kernel (kernels/fused_mlp) for the MLP gate/up pair
  "quantized" — the int8 weight path (kernels/quantized): per-channel
             weight scales, dynamic per-row activation quantization, i32
             accumulate, f32 de-scale.  Weights may be raw float leaves
             (quantized on the fly — the train-step fallback) or
             `QuantizedLinear` containers from `quantize_linear_params`
             (quantize-once at load; scales ride alongside the payload)

The Pallas paths carry a `jax.custom_vjp` whose backward routes the dgrad
and wgrad GEMMs back through the same dispatch — transposed shapes make
their own cache lookups, so forward and backward tile geometries tune
independently (as with flash attention's split fwd/bwd entries).  The
quantized path is inference-first: its backward falls back to the
high-precision tuned matmul route (a straight-through estimator — the int8
rounding is treated as identity for gradient purposes).

Weight casting to the activation dtype happens here (training params are
f32 master copies), so call sites pass raw param leaves; the serving engine
hands over weights already in the compute dtype (`cast_linear_params`),
where the cast is a no-op.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.fused_mlp.ops import fused_mlp_hidden
from ..kernels.fused_mlp.ref import fused_mlp_hidden_ref
from ..kernels.matmul.ops import matmul
from ..kernels.quantized.ops import int8_fused_mlp_hidden, int8_matmul
from ..quant import QuantizedTensor, quantize_weight

LINEAR_IMPLS = ("jnp", "pallas", "tuned", "fused", "quantized")

# The QuantizedLinear weight container IS repro.quant's QuantizedTensor —
# re-exported under the dispatch-layer name model code uses.
QuantizedLinear = QuantizedTensor


def resolve_impl(cfg) -> str:
    """ModelConfig -> linear_impl, tolerating configs predating the field."""
    return getattr(cfg, "linear_impl", "jnp")


def _check_impl(impl: str) -> None:
    if impl not in LINEAR_IMPLS:
        raise ValueError(
            f"unknown linear_impl {impl!r}; valid: {list(LINEAR_IMPLS)}")


class _LinearConfig(NamedTuple):
    """Static dispatch config threaded through the custom_vjp (hashable)."""
    tuned: bool
    hw_name: Optional[str]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _pallas_linear(cfg: _LinearConfig, x2, w):
    return matmul(x2, w, tuned=cfg.tuned, hw_name=cfg.hw_name)


def _pallas_linear_fwd(cfg, x2, w):
    return _pallas_linear(cfg, x2, w), (x2, w)


def _pallas_linear_bwd(cfg, res, g):
    x2, w = res
    # both transposed GEMMs stay on the Pallas path and key the cache with
    # their own (m, k, n): dgrad (m, n, k) and wgrad (k, m, n) tune
    # independently of the forward
    dx = matmul(g, w.T, tuned=cfg.tuned, hw_name=cfg.hw_name)
    dw = matmul(x2.T, g, tuned=cfg.tuned, hw_name=cfg.hw_name)
    return dx.astype(x2.dtype), dw.astype(w.dtype)


_pallas_linear.defvjp(_pallas_linear_fwd, _pallas_linear_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _quantized_linear(cfg: _LinearConfig, x2, w):
    """Float-weight quantized linear: weight quantizes per output channel on
    the fly, activation per row inside the kernel wrapper."""
    return int8_matmul(x2, w, tuned=cfg.tuned, hw_name=cfg.hw_name)


def _quantized_linear_fwd(cfg, x2, w):
    return _quantized_linear(cfg, x2, w), (x2, w)


def _quantized_linear_bwd(cfg, res, g):
    x2, w = res
    # straight-through: int8 rounding treated as identity, both grad GEMMs
    # take the high-precision tuned route (their own cache keys)
    dx = matmul(g, w.T, tuned=cfg.tuned, hw_name=cfg.hw_name)
    dw = matmul(x2.T, g, tuned=cfg.tuned, hw_name=cfg.hw_name)
    return dx.astype(x2.dtype), dw.astype(w.dtype)


_quantized_linear.defvjp(_quantized_linear_fwd, _quantized_linear_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _quantized_linear_frozen(cfg: _LinearConfig, x2, wq, wscale):
    """Prequantized-weight linear (QuantizedLinear container): the int8
    payload and scales pass straight to the kernel."""
    return int8_matmul(x2, QuantizedTensor(wq, wscale, -2), tuned=cfg.tuned,
                       hw_name=cfg.hw_name)


def _quantized_frozen_fwd(cfg, x2, wq, wscale):
    return _quantized_linear_frozen(cfg, x2, wq, wscale), (x2, wq, wscale)


def _quantized_frozen_bwd(cfg, res, g):
    x2, wq, wscale = res
    w = (wq.astype(jnp.float32) * wscale).astype(x2.dtype)
    dx = matmul(g, w.T, tuned=cfg.tuned, hw_name=cfg.hw_name)
    # int8 payloads carry float0 tangents (non-differentiable by
    # construction); the scales get symbolic zeros
    return (dx.astype(x2.dtype), np.zeros(wq.shape, jax.dtypes.float0),
            jnp.zeros_like(wscale))


_quantized_linear_frozen.defvjp(_quantized_frozen_fwd, _quantized_frozen_bwd)


# Param-leaf names of GEMM weights: every consumer casts them to the
# activation dtype right before the product.  `quantize_linear_params` takes
# the 2-D ones (consumed through `linear()`); `cast_linear_params` takes them
# at any rank (layer-stacked weights and expert stacks too).  Embeddings
# (indexed, and transposed for tied lm_heads), conv kernels, norm gains,
# biases and routers are NOT here: their consumers read them in float32 or
# outside a GEMM.
QUANT_WEIGHT_KEYS = frozenset({
    "wq", "wk", "wv", "wo",                       # attention projections
    "wq_down", "wq_up", "wkv_down", "wk_up", "wv_up",  # MLA projections
    "w_gate", "w_up", "w_down",                   # MLP
    "in_z", "in_x", "in_B", "in_C", "in_dt", "out_proj",  # SSM projections
    "lm_head",                                    # untied output head
})


def _leaf_name(path):
    """The innermost dict key on a pytree path (a param leaf's name)."""
    return next((p.key for p in reversed(path)
                 if isinstance(p, jax.tree_util.DictKey)), None)


def quantize_linear_params(params, dtype: str = "int8"):
    """Quantize-once-at-load: replace every 2-D float GEMM weight leaf
    (matched by name, see `QUANT_WEIGHT_KEYS`) with a `QuantizedLinear`
    container — int8 payload + per-output-channel f32 scales.
    `linear(impl="quantized")` consumes the containers directly, skipping
    the per-call weight quantization; all other leaves pass through."""
    def one(path, leaf):
        if (_leaf_name(path) in QUANT_WEIGHT_KEYS and getattr(leaf, "ndim", 0) == 2
                and jnp.issubdtype(leaf.dtype, jnp.floating)):
            return quantize_weight(leaf, dtype)
        return leaf
    return jax.tree_util.tree_map_with_path(one, params)


def cast_linear_params(params, dtype, *, tied: bool):
    """The tree with its GEMM weights in `dtype`: every floating leaf named
    in `QUANT_WEIGHT_KEYS`, at any rank, and `embed` when `tied` (it is then
    the head, through `linear()`).  Every other leaf (norm gains, biases,
    routers, an untied embedding, quantized containers) is returned as it
    is, and so is a leaf already in `dtype`: nothing is copied.  Exact: the
    products see the same operands as when they cast per call."""
    def one(path, leaf):
        name = _leaf_name(path)
        gemm = name in QUANT_WEIGHT_KEYS or (tied and name == "embed")
        if (gemm and not isinstance(leaf, QuantizedTensor)
                and jnp.issubdtype(leaf.dtype, jnp.floating)
                and leaf.dtype != dtype):
            return leaf.astype(dtype)
        return leaf
    return jax.tree_util.tree_map_with_path(
        one, params, is_leaf=lambda x: isinstance(x, QuantizedTensor))


def linear(x, w, *, impl: str = "jnp", hw_name: Optional[str] = None):
    """y = x @ w with dispatched execution.  x: (..., k); w: (k, n).

    Flattens the leading dims to one m axis before dispatch, so a (b, s, h)
    activation keys the tuning cache as (b*s, h, n) — exactly the shape
    `tuning.search.autotune_matmul` writes (the >2-D cache-miss fix).
    """
    _check_impl(impl)
    # named_scope is trace-time HLO metadata only (no runtime cost and no
    # program divergence when obs toggles), so it is applied unconditionally:
    # XLA profiles attribute every GEMM to its dispatch impl
    with jax.named_scope(f"linear_{impl}"):
        lead, k = x.shape[:-1], x.shape[-1]
        if impl == "quantized":
            cfg = _LinearConfig(tuned=True, hw_name=hw_name)
            if isinstance(w, QuantizedTensor):
                out = _quantized_linear_frozen(
                    cfg, x.reshape(-1, k), w.q, w.scale.reshape(1, -1))
                return out.reshape(*lead, w.q.shape[-1])
            out = _quantized_linear(cfg, x.reshape(-1, k), w.astype(x.dtype))
            return out.reshape(*lead, w.shape[-1])
        w = w.astype(x.dtype)
        if impl == "jnp":
            return x @ w
        cfg = _LinearConfig(tuned=impl in ("tuned", "fused"), hw_name=hw_name)
        out = _pallas_linear(cfg, x.reshape(-1, k), w)
        return out.reshape(*lead, w.shape[-1])


def expert_linear(x, w, *, impl: str = "jnp", hw_name: Optional[str] = None):
    """Batched per-expert GEMM: x (e, m, k) @ w (e, k, n) -> (e, m, n).

    The jnp path keeps the einsum (XLA lowers it to one batched GEMM, the
    `moe_expert_*` entry core/transformer_gemms enumerates).  Pallas paths
    run one kernel per expert under `lax.map` — the TPU grid is sequential
    per core anyway, and every expert shares one (m, k, n) cache key.
    """
    _check_impl(impl)
    with jax.named_scope(f"expert_linear_{impl}"):
        w = w.astype(x.dtype)
        if impl == "jnp":
            return jnp.einsum("emk,ekn->emn", x, w)
        if impl == "quantized":
            qcfg = _LinearConfig(tuned=True, hw_name=hw_name)
            # per-expert dynamic quantization: every expert shares one
            # (m, k, n) cache key, like the float Pallas path below
            return jax.lax.map(
                lambda xw: _quantized_linear(qcfg, xw[0], xw[1]), (x, w))
        cfg = _LinearConfig(tuned=impl in ("tuned", "fused"), hw_name=hw_name)
        return jax.lax.map(lambda xw: _pallas_linear(cfg, xw[0], xw[1]),
                           (x, w))


def fused_mlp(x, p, cfg, *, impl: Optional[str] = None,
              hw_name: Optional[str] = None):
    """Full MLP block through the fused Pallas hidden kernel + dispatched
    down projection.  p: {w_gate (swiglu), w_up, w_down}; x: (..., h).

    The gate/up GEMM pair and the elementwise combine run as ONE Pallas
    kernel (kernels/fused_mlp) with its recompute-based custom-VJP backward;
    both the hidden kernel and the down GEMM consult the tuning cache.
    """
    impl = impl or resolve_impl(cfg)
    dt = x.dtype
    with jax.named_scope("fused_mlp"):
        w_gate = p["w_gate"].astype(dt) if cfg.mlp_type == "swiglu" else None
        hidden = fused_mlp_hidden(
            x, w_gate, p["w_up"].astype(dt), mlp_type=cfg.mlp_type,
            tuned=True, hw_name=hw_name)
        return linear(hidden, p["w_down"], impl="tuned", hw_name=hw_name)


class _QuantMLPConfig(NamedTuple):
    """Static dispatch config for the quantized fused-MLP custom_vjp."""
    mlp_type: str
    hw_name: Optional[str]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _quantized_hidden(cfg: _QuantMLPConfig, x2, w_gate, w_up):
    return int8_fused_mlp_hidden(x2, w_gate, w_up, mlp_type=cfg.mlp_type,
                                 tuned=True, hw_name=cfg.hw_name)


def _quantized_hidden_fwd(cfg, x2, w_gate, w_up):
    return _quantized_hidden(cfg, x2, w_gate, w_up), (x2, w_gate, w_up)


def _quantized_hidden_bwd(cfg, res, g):
    # straight-through fallback: recompute the hidden in high precision and
    # differentiate the reference (the int8 forward only affects the primal)
    x2, w_gate, w_up = res
    if w_gate is None:
        _, vjp = jax.vjp(
            lambda x, wu: fused_mlp_hidden_ref(x, None, wu, cfg.mlp_type),
            x2, w_up)
        dx, dwu = vjp(g)
        return dx.astype(x2.dtype), None, dwu.astype(w_up.dtype)
    _, vjp = jax.vjp(
        lambda x, wg, wu: fused_mlp_hidden_ref(x, wg, wu, cfg.mlp_type),
        x2, w_gate, w_up)
    dx, dwg, dwu = vjp(g)
    return (dx.astype(x2.dtype), dwg.astype(w_gate.dtype),
            dwu.astype(w_up.dtype))


_quantized_hidden.defvjp(_quantized_hidden_fwd, _quantized_hidden_bwd)


def quantized_mlp(x, p, cfg, *, hw_name: Optional[str] = None):
    """Full MLP block on the int8 path: the gate/up pair runs the int8
    fused-MLP kernel (one i32-accumulating pass), the down projection the
    quantized linear.  Float weight leaves quantize on the fly and keep the
    high-precision gradient fallback; `QuantizedLinear` containers (from
    `quantize_linear_params`) skip re-quantization — the inference path."""
    lead, h = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, h)
    w_gate = p.get("w_gate") if cfg.mlp_type == "swiglu" else None
    w_up = p["w_up"]
    with jax.named_scope("quantized_mlp"):
        if isinstance(w_up, QuantizedTensor):
            hidden = int8_fused_mlp_hidden(
                x2, w_gate, w_up, mlp_type=cfg.mlp_type, tuned=True,
                hw_name=hw_name)
        else:
            qcfg = _QuantMLPConfig(cfg.mlp_type, hw_name)
            hidden = _quantized_hidden(
                qcfg, x2,
                None if w_gate is None else w_gate.astype(x.dtype),
                w_up.astype(x.dtype))
        f = hidden.shape[-1]
        out = linear(hidden.reshape(*lead, f), p["w_down"], impl="quantized",
                     hw_name=hw_name)
        return out


def expert_fused_hidden(x, w_gate, w_up, *, mlp_type: str,
                        hw_name: Optional[str] = None):
    """Per-expert fused hidden: x (e, m, h) with (e, h, f) expert weights ->
    (e, m, f), one fused kernel per expert under `lax.map` (the MoE
    counterpart of `fused_mlp`'s hidden half)."""
    dt = x.dtype
    wu = w_up.astype(dt)
    if mlp_type == "swiglu":
        return jax.lax.map(
            lambda t: fused_mlp_hidden(t[0], t[1], t[2], mlp_type=mlp_type,
                                       tuned=True, hw_name=hw_name),
            (x, w_gate.astype(dt), wu))
    return jax.lax.map(
        lambda t: fused_mlp_hidden(t[0], None, t[1], mlp_type=mlp_type,
                                   tuned=True, hw_name=hw_name),
        (x, wu))
