"""Grouped matrix product for drop-free MoE: the rows routed to each held
expert, sorted by expert, times that expert's weights, in one call.

    lhs (m, k) rows sorted by group; rhs (g, k, n) one matrix per group;
    group_sizes (g + 1,) rows of each group, the last entry the rows that
    belong to no held expert (they sit at the end; their output rows are
    not computed, and hold whatever the buffer held).

On the chip this is the Pallas grouped-matmul kernel that ships with JAX
(`jax.experimental.pallas.ops.tpu.megablox`): its grid runs over the row
tiles that groups actually occupy, so its work follows the routed rows and
not m.  It is called unjitted inside `_moe_gmm_jit`, the stable name the
trace finds it by.  Elsewhere it runs in Pallas interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..backend import interpret_mode

TILE_K = 1024               # contraction and output tiles: one weight tile of
TILE_N = 1024               # 2 MiB in bf16, double-buffered well inside VMEM


def row_tile(m: int) -> int:
    """Rows per tile: 16 (a packed bf16 sublane tile) for decode-sized
    calls, where each expert gets a few rows, 128 for prefill-sized ones."""
    return 16 if m <= 1024 else 128


@functools.partial(jax.jit, static_argnames=("interpret",))
def _moe_gmm_jit(lhs, rhs, group_sizes, layer, *, interpret: bool):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    nl, g, k, n = rhs.shape
    m = lhs.shape[0]
    tm = row_tile(m)
    mp = -(-m // tm) * tm
    rest = group_sizes[-1] + (mp - m)   # the padding rows join the rest
    if mp != m:
        lhs = jnp.pad(lhs, ((0, mp - m), (0, 0)))
    # every layer's groups in one (nl * g, k, n) stack, a bitcast of the
    # weights; the groups of other layers are empty, so the kernel visits
    # only this layer's experts and reads their weights in place
    sizes = jax.lax.dynamic_update_slice(
        jnp.zeros((nl * g + 1,), jnp.int32), group_sizes[:g], (layer * g,))
    sizes = sizes.at[-1].set(rest)
    out = gmm.__wrapped__(lhs, rhs.reshape(nl * g, k, n).astype(lhs.dtype),
                          sizes, preferred_element_type=lhs.dtype,
                          tiling=(tm, min(TILE_K, k), min(TILE_N, n)),
                          interpret=interpret)
    return out[:m]


def grouped_matmul(lhs, rhs, group_sizes, *, interpret: Optional[bool] = None):
    """lhs (m, k) @ rhs[group] for each row's group; see the module
    docstring.  rhs is (g, k, n), or `models.layers.LayerOf` a (layers, g,
    k, n) stack, read in place.  Returns (m, n) in lhs's dtype; the
    trailing group's rows are undefined."""
    from ...models.layers import LayerOf
    stack, layer = (rhs.stack, rhs.index) if isinstance(rhs, LayerOf) \
        else (rhs[None], 0)
    assert group_sizes.shape == (stack.shape[1] + 1,), \
        (group_sizes.shape, stack.shape)
    return _moe_gmm_jit(lhs, stack, group_sizes.astype(jnp.int32),
                        jnp.asarray(layer, jnp.int32),
                        interpret=interpret_mode(interpret))
