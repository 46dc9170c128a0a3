"""Mixture-of-Experts layer: top-k routing, capacity-bounded sort-based
dispatch, EP-shardable batched-expert GEMMs, shared experts.

The expert FFN computation is `num_experts` batched GEMMs of size
(capacity, h) x (h, moe_d_ff) — exactly the `moe_expert_*` entries that
core/transformer_gemms.py enumerates, so the paper's alignment rules apply to
(capacity, h, moe_d_ff) and the advisor checks experts % EP == 0.

Dispatch is sort-based (GShard-style but without the T×E×C one-hot): tokens
are sorted by assigned expert, positioned within their expert's capacity
window, and scattered into an (E, C, h) buffer.  Under EP sharding of the
leading expert axis XLA lowers the scatter/gather to all-to-all traffic.

`apply_moe_dropless` is the serving path: no capacity, so a token's output
never depends on its batch neighbours.  The (token, expert) pairs routed to
the held experts (all of them, or an expert share: `cfg.experts_held`) are
sorted by expert into one grouped matrix product per weight
(`kernels.grouped_matmul`), whose work follows the routed rows.

Routers (`route`): softmax top-k renormalised, or deepseek-v3's sigmoid
scores with a selection-only bias, group-limited top-k and a routed scale.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import dense_init
from .linear import expert_fused_hidden, expert_linear, linear, resolve_impl
from .mlp import apply_mlp, init_mlp


def init_moe(key, cfg: ModelConfig):
    """Router over all `num_experts`; weights of the held experts only."""
    h, f, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    eh = cfg.num_held_experts
    ks = jax.random.split(key, 5)
    out_scale = 1.0 / (2 * cfg.num_layers) ** 0.5
    p = {
        "router": dense_init(ks[0], h, e, scale=0.5),
        "w_up": jax.vmap(lambda k: dense_init(k, h, f))(jax.random.split(ks[1], eh)),
        "w_down": jax.vmap(lambda k: dense_init(k, f, h, scale=out_scale))(
            jax.random.split(ks[2], eh)),
    }
    if cfg.mlp_type == "swiglu":
        p["w_gate"] = jax.vmap(lambda k: dense_init(k, h, f))(jax.random.split(ks[3], eh))
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=cfg.moe_d_ff * cfg.num_shared_experts)
    if cfg.router_score == "sigmoid":
        # selection-only bias (deepseek-v3's e_score_correction_bias)
        p["router_bias"] = jnp.zeros((e,), jnp.float32)
    return p


def route(p, xt, cfg: ModelConfig):
    """xt: (t, h) -> (weights (t, k) f32, expert ids (t, k) int32, probs
    (t, e) f32 for the load-balance loss).  Experts are scored over all
    `num_experts`, held or not."""
    e, k = cfg.num_experts, cfg.top_k
    if cfg.router_score == "softmax":
        logits = linear(xt, p["router"], impl=resolve_impl(cfg)).astype(
            jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, k)
        gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)
        return gate * cfg.routed_scale, idx, probs
    if cfg.router_score != "sigmoid":
        raise ValueError(f"unknown router_score {cfg.router_score!r}")
    # deepseek-v3 scores its experts in float32: a bf16 logit moves the
    # choice among near-equal experts
    logits = jnp.dot(xt.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    sel = scores + p["router_bias"].astype(jnp.float32)
    g = cfg.router_groups
    if g > 1:
        t = sel.shape[0]
        # a group's score is the sum of its two best experts; only the
        # best `router_topk_groups` groups stay selectable
        best2 = jax.lax.top_k(sel.reshape(t, g, e // g), 2)[0].sum(-1)
        _, gidx = jax.lax.top_k(best2, cfg.router_topk_groups)
        keep = jnp.zeros((t, g), bool).at[
            jnp.arange(t)[:, None], gidx].set(True)
        sel = jnp.where(jnp.repeat(keep, e // g, axis=1), sel, -jnp.inf)
    _, idx = jax.lax.top_k(sel, k)
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    gate = gate / (gate.sum(-1, keepdims=True) + 1e-20)
    return gate * cfg.routed_scale, idx, scores / scores.sum(-1, keepdims=True)


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    cap = int(tokens * cfg.top_k * cfg.moe_capacity_factor / cfg.num_experts)
    return max(cap - cap % -8, 8)  # round up to 8 (sublane alignment)


def apply_moe(p, x, cfg: ModelConfig):
    """x: (b, s, h) -> (y, aux_loss)."""
    b, s, h = x.shape
    e, k = cfg.num_experts, cfg.top_k
    xt = x.reshape(b * s, h)
    t = b * s
    cap = _capacity(t, cfg)
    impl = resolve_impl(cfg)

    if cfg.num_held_experts != e:
        raise NotImplementedError(
            "the capacity path holds every expert; an expert share "
            "(experts_held) runs moe_dispatch='dropless'")
    gate, idx, probs = route(p, xt, cfg)  # (t, k)

    # load-balance auxiliary loss (Switch/GShard form)
    frac_tokens = jnp.mean(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)

    # ---- sort-based dispatch -------------------------------------------------
    flat_expert = idx.reshape(-1)  # (t*k,)
    flat_token = jnp.repeat(jnp.arange(t), k)
    flat_gate = gate.reshape(-1)
    order = jnp.argsort(flat_expert, stable=True)
    se, st, sg = flat_expert[order], flat_token[order], flat_gate[order]
    # position of each entry within its expert's token run
    seg_start = jnp.searchsorted(se, jnp.arange(e), side="left")
    pos = jnp.arange(t * k) - seg_start[se]
    keep = pos < cap
    # scatter into (e, cap, h); dropped tokens go to a trash row
    from ..parallel.sharding import constrain
    buf = jnp.zeros((e * cap, h), x.dtype)
    dst = jnp.where(keep, se * cap + pos, e * cap - 1)
    buf = buf.at[dst].add(jnp.where(keep[:, None], xt[st], 0))
    buf = constrain(buf, "eh").reshape(e, cap, h)

    # ---- batched expert GEMMs (E x (cap,h)x(h,f)) ----------------------------
    # dispatched through repro.models.linear: jnp keeps the einsum, Pallas
    # impls run one tuned kernel per expert, and "fused" runs the gate/up
    # pair + combine as the fused MLP kernel per expert
    if impl == "fused":
        hdn = expert_fused_hidden(
            buf, p.get("w_gate"), p["w_up"],
            mlp_type="swiglu" if cfg.mlp_type == "swiglu" else "gelu")
    elif cfg.mlp_type == "swiglu":
        g = jax.nn.silu(expert_linear(buf, p["w_gate"], impl=impl))
        u = expert_linear(buf, p["w_up"], impl=impl)
        hdn = g * u
    else:
        hdn = jax.nn.gelu(expert_linear(buf, p["w_up"], impl=impl))
    out_buf = expert_linear(hdn, p["w_down"], impl=impl)
    out_buf = out_buf.reshape(e * cap, h)

    # ---- gather back + combine ----------------------------------------------
    # anchor the token-major layout: without it the SPMD partitioner
    # replicates the (t*k, h) gather output on every chip (measured 60 GB x
    # 58 layers x n_micro on deepseek-v3 — EXPERIMENTS.md §Perf)
    out_buf = constrain(out_buf, "eh")
    picked = jnp.where(keep[:, None], out_buf[dst], 0)
    picked = constrain(picked, "td")
    y = jnp.zeros((t, h), x.dtype).at[st].add(picked * sg[:, None].astype(x.dtype))
    y = constrain(y, "td")

    if cfg.num_shared_experts:
        y = y + apply_mlp(p["shared"], xt, cfg)
    return y.reshape(b, s, h), aux


MOE_STATS = 4   # routed_held, routed_total, held_rows_max, held_active
EXPERT_KEYS = ("w_gate", "w_up", "w_down")     # (held experts, in, out)


def no_moe_stats():
    return jnp.zeros((MOE_STATS,), jnp.int32)


def merge_moe_stats(a, b):
    """Stats of two layers (or steps) together: pair and expert counts
    add, the largest expert's rows take the max."""
    return jnp.stack([a[0] + b[0], a[1] + b[1], jnp.maximum(a[2], b[2]),
                      a[3] + b[3]])


def apply_moe_dropless(p, x, cfg: ModelConfig, token_mask=None):
    """Drop-free MoE over the held experts.  x: (b, s, h); token_mask:
    (b, s) bool, the rows that hold a token (dead decode rows and prefill
    padding route nowhere).  The expert weights may be `LayerOf` a stack of
    layers (the grouped kernel reads the layer in place).  Returns (y,
    stats) with stats int32 (MOE_STATS,): (token, expert) pairs on the held
    experts, pairs over all experts, and the most rows any held expert
    got."""
    from ..kernels.grouped_matmul.ops import grouped_matmul
    b, s, h = x.shape
    t, k = b * s, cfg.top_k
    held, first = cfg.num_held_experts, cfg.first_held_expert
    xt = x.reshape(t, h)
    live = (jnp.ones((t,), bool) if token_mask is None
            else token_mask.reshape(t))
    with jax.named_scope("moe_router"):
        gate, idx, _ = route(p, xt, cfg)
    with jax.named_scope("moe_dispatch"):
        local = idx - first
        mine = (local >= 0) & (local < held) & live[:, None]
        group = jnp.where(mine, local, held).reshape(-1)    # held = no expert
        order = jnp.argsort(group, stable=True)
        tok = order // k
        sizes = jnp.bincount(group, length=held + 1).astype(jnp.int32)
        rows = xt[tok]                                      # (t * k, h)
    with jax.named_scope("moe_experts"):
        if cfg.mlp_type == "swiglu":
            hdn = jax.nn.silu(grouped_matmul(rows, p["w_gate"], sizes)) \
                * grouped_matmul(rows, p["w_up"], sizes)
        else:
            hdn = jax.nn.gelu(grouped_matmul(rows, p["w_up"], sizes))
        out = grouped_matmul(hdn, p["w_down"], sizes)
    with jax.named_scope("moe_combine"):
        # back to (token, choice) order; the weighted sum over a token's
        # choices accumulates in f32.  The kernel never writes the rest
        # group's rows, so they are masked, not weighted by zero
        back = out[jnp.argsort(order)].reshape(t, k, h)
        back = jnp.where(mine[..., None], back, jnp.zeros((), back.dtype))
        y = jnp.einsum("tk,tkh->th", jnp.where(mine, gate, 0.0), back,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    if cfg.num_shared_experts:
        y = y + apply_mlp(p["shared"], xt, cfg)
    stats = jnp.stack([sizes[:held].sum(), live.sum() * k,
                       sizes[:held].max(),
                       (sizes[:held] > 0).sum()]).astype(jnp.int32)
    return y.reshape(b, s, h), stats
