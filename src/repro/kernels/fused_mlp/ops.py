"""jit'd public wrapper for the fused SwiGLU/MLP hidden kernel.

`fused_mlp_hidden` takes model-layout activations (..., h), flattens the
leading dims to 2-D — producing exactly the (m, h, f) key the autotuner
writes — pads misaligned problems up to the block grid, and dispatches to
the Pallas kernel (TPU) or the jnp oracle (use_pallas=False).

The Pallas path is *differentiable*: a jax.custom_vjp pairs the forward
kernel with the recompute-based Pallas backward in `backward.py` (dx and
dw grids), so `linear_impl="fused"` trains end-to-end on the measured
kernels — the same forward/backward pattern as flash attention.

With `tuned=True` the wrapper consults the autotuning cache
(`repro.tuning.cache`) for a measured-best (block_m, block_f, block_k) for
this exact (m, h, f, dtype, hw) before falling back to the 128^3 defaults —
see `repro.tuning.search.autotune_fused_mlp` for how entries are produced.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ... import obs
from ...core.hardware import get_hardware
from ...core.quantization import round_up
from ...tuning.cache import lookup as _tuning_lookup
from ..backend import interpret_mode
from .backward import fused_mlp_bwd_pallas
from .kernel import fused_mlp_pallas
from .ref import fused_mlp_hidden_ref, is_gated


def fused_mlp_op_name(mlp_type: str) -> str:
    """Tuning-cache op key: fused_mlp_swiglu | fused_mlp_gelu | ..."""
    return f"fused_mlp_{mlp_type}"


class _FusedConfig(NamedTuple):
    """Static kernel config threaded through the custom_vjp (hashable)."""
    mlp_type: str
    block_m: int
    block_f: int
    block_k: int
    bwd_block_m: int
    bwd_block_f: int
    interpret: bool


def _pad2(x, m, n):
    pm, pn = m - x.shape[0], n - x.shape[1]
    if pm == 0 and pn == 0:
        return x
    return jnp.pad(x, ((0, pm), (0, pn)))


def _fwd_call(cfg: _FusedConfig, x, w_gate, w_up):
    m, h = x.shape
    f = w_up.shape[1]
    mp = round_up(m, cfg.block_m)
    hp = round_up(h, cfg.block_k)
    fp = round_up(f, cfg.block_f)
    out = fused_mlp_pallas(
        _pad2(x, mp, hp),
        None if w_gate is None else _pad2(w_gate, hp, fp),
        _pad2(w_up, hp, fp), mlp_type=cfg.mlp_type, block_m=cfg.block_m,
        block_f=cfg.block_f, block_k=cfg.block_k, interpret=cfg.interpret)
    return out[:m, :f]


def _bwd_call(cfg: _FusedConfig, x, w_gate, w_up, dh):
    m, h = x.shape
    f = w_up.shape[1]
    mp = round_up(m, cfg.bwd_block_m)
    fp = round_up(f, cfg.bwd_block_f)
    # padded dh rows/columns are zero, so dg/du vanish there: the padding
    # contributes exactly zero to dx and to the sliced-off dw columns
    dx, dwg, dwu = fused_mlp_bwd_pallas(
        _pad2(x, mp, h),
        None if w_gate is None else _pad2(w_gate, h, fp),
        _pad2(w_up, h, fp), _pad2(dh, mp, fp), mlp_type=cfg.mlp_type,
        block_m=cfg.bwd_block_m, block_f=cfg.bwd_block_f,
        interpret=cfg.interpret)
    dx = dx[:m].astype(x.dtype)
    dwu = dwu[:, :f].astype(w_up.dtype)
    if dwg is None:
        return dx, dwu
    return dx, dwg[:, :f].astype(w_gate.dtype), dwu


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_gated(cfg: _FusedConfig, x, w_gate, w_up):
    return _fwd_call(cfg, x, w_gate, w_up)


def _fused_gated_fwd(cfg, x, w_gate, w_up):
    return _fwd_call(cfg, x, w_gate, w_up), (x, w_gate, w_up)


def _fused_gated_bwd(cfg, res, dh):
    x, w_gate, w_up = res
    return _bwd_call(cfg, x, w_gate, w_up, dh)


_fused_gated.defvjp(_fused_gated_fwd, _fused_gated_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_plain(cfg: _FusedConfig, x, w_up):
    return _fwd_call(cfg, x, None, w_up)


def _fused_plain_fwd(cfg, x, w_up):
    return _fwd_call(cfg, x, None, w_up), (x, w_up)


def _fused_plain_bwd(cfg, res, dh):
    x, w_up = res
    return _bwd_call(cfg, x, None, w_up, dh)


_fused_plain.defvjp(_fused_plain_fwd, _fused_plain_bwd)


@functools.partial(jax.jit, static_argnames=(
    "mlp_type", "block_m", "block_f", "block_k", "bwd_block_m", "bwd_block_f",
    "interpret", "use_pallas"))
def _fused_jit(x, w_gate, w_up, *, mlp_type: str, block_m: int, block_f: int,
               block_k: int, bwd_block_m: int, bwd_block_f: int,
               interpret: bool, use_pallas: bool):
    if not use_pallas:
        return fused_mlp_hidden_ref(x, w_gate, w_up, mlp_type)
    cfg = _FusedConfig(mlp_type=mlp_type, block_m=block_m, block_f=block_f,
                       block_k=block_k, bwd_block_m=bwd_block_m,
                       bwd_block_f=bwd_block_f, interpret=interpret)
    if is_gated(mlp_type):
        return _fused_gated(cfg, x, w_gate, w_up)
    return _fused_plain(cfg, x, w_up)


def fused_mlp_hidden(x, w_gate, w_up, *, mlp_type: str = "swiglu",
                     block_m: int = 128, block_f: int = 128,
                     block_k: int = 128, bwd_block_m: int = 128,
                     bwd_block_f: int = 128,
                     interpret: Optional[bool] = None,
                     use_pallas: bool = True, tuned: bool = False,
                     hw_name: Optional[str] = None):
    """hidden = act-combine(x @ w_gate, x @ w_up).  x: (..., h) -> (..., f).

    Differentiable: the Pallas path carries a custom VJP onto the
    recompute-based backward kernels (backward.py), so this op can sit
    inside value_and_grad / train_step.  (bwd_block_m, bwd_block_f) block
    the backward grids independently of the forward.

    tuned=True overrides (block_m, block_f, block_k) with the autotuning
    cache's measured-best config for this exact flattened (m, h, f) problem
    when one exists (cache misses keep the defaults).  The lookup runs at
    trace time, outside the jit, against the same key
    `tuning.search.autotune_fused_mlp` writes.
    """
    lead, h = x.shape[:-1], x.shape[-1]
    f = w_up.shape[-1]
    m = 1
    for d in lead:
        m *= d
    if not is_gated(mlp_type):
        w_gate = None
    tuned_hit = None
    if tuned and use_pallas:
        cfg = _tuning_lookup(fused_mlp_op_name(mlp_type), (m, h, f),
                             jnp.dtype(x.dtype).name,
                             hw_name or get_hardware().name)
        tuned_hit = cfg is not None
        if cfg is not None:
            block_m = cfg.blocks["block_m"]
            block_f = cfg.blocks["block_f"]
            block_k = cfg.blocks["block_k"]
    if obs.enabled():
        obs.record_dispatch(
            fused_mlp_op_name(mlp_type),
            impl="pallas" if use_pallas else "jnp", shape=(m, h, f),
            blocks={"block_m": block_m, "block_f": block_f,
                    "block_k": block_k} if use_pallas else None,
            tuned_hit=tuned_hit)
    out = _fused_jit(x.reshape(m, h), w_gate, w_up, mlp_type=mlp_type,
                     block_m=block_m, block_f=block_f, block_k=block_k,
                     bwd_block_m=bwd_block_m, bwd_block_f=bwd_block_f,
                     interpret=interpret_mode(interpret),
                     use_pallas=use_pallas)
    return out.reshape(*lead, f)
