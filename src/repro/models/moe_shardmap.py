"""Expert-parallel MoE with EXPLICIT collective scheduling (shard_map).

The auto-SPMD dispatch (models/moe.py) lets XLA choose the collectives for
the token->expert scatter and the expert->token combine; even with output
sharding anchors it emits multi-pass f32 gathers/all-reduces (measured
11 TB/chip on deepseek-v3 train_4k — EXPERIMENTS.md §Perf).

This path exploits the framework's activation layout directly: tokens are
sharded over `data` and REPLICATED over `model` (= the EP axis), so

  * expert selection, capacity packing, and the expert FFN are fully LOCAL
    to each (data, model) shard: each chip packs only the tokens routed to
    ITS E/t resident experts — no dispatch communication at all;
  * the combine is exactly ONE bf16 `psum` of the (t_local, h) partial
    outputs over `model` per layer — each chip contributes the share of
    every token's top-k that its experts produced.

Per layer per microbatch the communication is t_loc x h x 2 bytes
(deepseek-v3/mb4: 235 MB/chip vs the ~3 GB x multiple passes XLA chose).
Selected via ModelConfig.moe_dispatch == "shard_map"; requires the
activation context to carry the mesh (launchers set it), otherwise falls
back to the auto-SPMD path (CPU unit tests, single device).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from .linear import expert_fused_hidden, expert_linear, linear, resolve_impl
from .mlp import apply_mlp


def _local_block(cfg: ModelConfig, tp_axis: str):
    e, k = cfg.num_experts, cfg.top_k

    def block(router, w_gate, w_up, w_down, xt):
        """Per-shard block.  router: (h, E) replicated; w_*: (E_loc, h, f)
        this shard's experts; xt: (t_loc, h) this data shard's tokens
        (replicated over the model axis)."""
        t_loc, h = xt.shape
        e_loc = w_up.shape[0]
        m = jax.lax.axis_index(tp_axis)
        lo = m * e_loc
        impl = resolve_impl(cfg)

        logits = linear(xt, router, impl=impl).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, k)                  # (t_loc, k)
        gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)

        # load-balance aux (identical on every model shard: same tokens)
        frac_tokens = jnp.mean(jax.nn.one_hot(idx, e, dtype=jnp.float32),
                               axis=(0, 1))
        aux = e * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))

        # ---- local packing: only assignments landing on OUR experts ------
        cap = max(int(t_loc * k * cfg.moe_capacity_factor / e) // -8 * -8, 8)
        flat_idx = idx.reshape(-1)                           # (t_loc*k,)
        flat_tok = jnp.repeat(jnp.arange(t_loc), k)
        flat_gate = gate.reshape(-1).astype(xt.dtype)
        local = (flat_idx >= lo) & (flat_idx < lo + e_loc)
        le = jnp.where(local, flat_idx - lo, e_loc)          # e_loc = trash
        order = jnp.argsort(le, stable=True)
        se, st, sg = le[order], flat_tok[order], flat_gate[order]
        seg_start = jnp.searchsorted(se, jnp.arange(e_loc), side="left")
        pos = jnp.arange(t_loc * k) - seg_start[jnp.clip(se, 0, e_loc - 1)]
        keep = (se < e_loc) & (pos < cap)
        dst = jnp.where(keep, se * cap + pos, e_loc * cap - 1)
        buf = jnp.zeros((e_loc * cap, h), xt.dtype)
        buf = buf.at[dst].add(jnp.where(keep[:, None], xt[st], 0))
        buf = buf.reshape(e_loc, cap, h)

        # ---- local expert FFN (dispatched through models.linear) ---------
        if impl == "fused":
            hdn = expert_fused_hidden(
                buf, w_gate, w_up,
                mlp_type="swiglu" if cfg.mlp_type == "swiglu" else "gelu")
        elif cfg.mlp_type == "swiglu":
            g = jax.nn.silu(expert_linear(buf, w_gate, impl=impl))
            u = expert_linear(buf, w_up, impl=impl)
            hdn = g * u
        else:
            hdn = jax.nn.gelu(expert_linear(buf, w_up, impl=impl))
        out_buf = expert_linear(hdn, w_down, impl=impl).reshape(e_loc * cap, h)

        # ---- local combine + ONE psum over the EP axis -------------------
        picked = jnp.where(keep[:, None], out_buf[dst], 0)
        y = jnp.zeros((t_loc, h), xt.dtype).at[st].add(picked * sg[:, None])
        y = jax.lax.psum(y, tp_axis)
        return y, aux

    return block


def apply_moe_shardmap(p, x, cfg: ModelConfig):
    """x: (b, s, h) -> (y, aux).  Falls back to auto-SPMD when no mesh."""
    from ..parallel.sharding import activation_context
    ctx = activation_context()
    mesh = ctx.get("mesh")
    if mesh is None or ctx.get("tp") is None:
        from .moe import apply_moe
        return apply_moe(p, x, cfg)
    tp_axis = ctx["tp"]
    dp = ctx["dp"] or ()
    b, s, h = x.shape
    xt = x.reshape(b * s, h)

    block = _local_block(cfg, tp_axis)
    spec_tok = P(dp, None)
    spec_exp = P(tp_axis, None, None)
    y, aux = jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(None, None), spec_exp, spec_exp, spec_exp, spec_tok),
        out_specs=(spec_tok, P()), check_vma=False,
    )(p["router"], p.get("w_gate", p["w_up"]), p["w_up"], p["w_down"], xt)

    if cfg.num_shared_experts:
        y = y + apply_mlp(p["shared"], xt, cfg)
    return y.reshape(b, s, h), aux
