"""MoE dispatch invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.mlp import apply_mlp
from repro.models.moe import _capacity, apply_moe, init_moe

KEY = jax.random.PRNGKey(5)


def moe_cfg(**kw):
    base = dict(name="m", family="moe", num_layers=2, d_model=32,
                num_heads=4, num_kv_heads=4, d_ff=64, vocab_size=128,
                mlp_type="swiglu", num_experts=4, top_k=2, moe_d_ff=48,
                moe_capacity_factor=8.0, dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def test_identical_experts_equal_single_mlp():
    """With every expert's weights identical and no drops, MoE(x) == MLP(x)
    (gates renormalize to 1)."""
    cfg = moe_cfg()
    p = init_moe(KEY, cfg)
    for k in ("w_up", "w_gate", "w_down"):
        p[k] = jnp.broadcast_to(p[k][0], p[k].shape)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model)) * 0.5
    y, aux = apply_moe(p, x, cfg)

    mlp_params = {"w_gate": p["w_gate"][0], "w_up": p["w_up"][0],
                  "w_down": p["w_down"][0]}
    cfg_dense = dataclasses.replace(cfg, d_ff=cfg.moe_d_ff)
    want = apply_mlp(mlp_params, x.reshape(-1, cfg.d_model), cfg_dense)
    if cfg.num_shared_experts:
        want = want + apply_mlp(p["shared"], x.reshape(-1, cfg.d_model), cfg_dense)
    np.testing.assert_allclose(np.asarray(y.reshape(-1, cfg.d_model)),
                               np.asarray(want), atol=1e-4, rtol=1e-4)


def test_aux_loss_bounds():
    """Load-balance loss is >= 1 (perfectly balanced) and finite."""
    cfg = moe_cfg()
    p = init_moe(KEY, cfg)
    x = jax.random.normal(KEY, (2, 64, cfg.d_model))
    _, aux = apply_moe(p, x, cfg)
    assert np.isfinite(float(aux))
    assert float(aux) >= 0.99  # E * sum(f_i P_i) >= 1 by Cauchy-Schwarz


def test_capacity_drops_are_bounded():
    """With tiny capacity, output is still finite and shaped."""
    cfg = moe_cfg(moe_capacity_factor=0.25)
    p = init_moe(KEY, cfg)
    x = jax.random.normal(KEY, (2, 64, cfg.d_model))
    y, _ = apply_moe(p, x, cfg)
    assert y.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(y)))


def test_capacity_alignment():
    """Capacity is rounded up to the 8-row sublane tile (paper alignment)."""
    cfg = moe_cfg()
    assert _capacity(1024, cfg) % 8 == 0


def test_grad_flows_through_dispatch():
    cfg = moe_cfg()
    p = init_moe(KEY, cfg)
    x = jax.random.normal(KEY, (1, 32, cfg.d_model)) * 0.5

    def loss(p):
        y, aux = apply_moe(p, x, cfg)
        return jnp.sum(y ** 2) + 0.01 * aux

    g = jax.grad(loss)(p)
    gnorm = sum(float(jnp.sum(jnp.abs(t))) for t in jax.tree.leaves(g))
    assert np.isfinite(gnorm) and gnorm > 0
    # router must receive gradient (through the gate weights)
    assert float(jnp.sum(jnp.abs(g["router"]))) > 0


def v3_cfg(**kw):
    """A small deepseek-v3-style layer: sigmoid scores with a selection
    bias, 4 groups of 4 experts (best 2 groups kept), top-4, scale 2.5."""
    return moe_cfg(num_experts=16, top_k=4, num_shared_experts=1,
                   router_score="sigmoid", router_groups=4,
                   router_topk_groups=2, routed_scale=2.5, **kw)


def _v3_params(cfg):
    p = init_moe(KEY, cfg)
    p["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(9), (16,))
    return p


def test_v3_router_groups_and_weights():
    """Chosen experts lie in the best groups by their two best biased
    scores; weights are the unbiased scores normalised over the chosen,
    times 2.5."""
    from repro.models.moe import route
    cfg = v3_cfg()
    p = _v3_params(cfg)
    xt = jax.random.normal(KEY, (64, cfg.d_model))
    gate, idx, _ = route(p, xt, cfg)
    s = jax.nn.sigmoid(xt @ p["router"])
    sel = np.asarray(s + p["router_bias"])
    g = np.sort(sel.reshape(64, 4, 4), -1)[..., -2:].sum(-1)
    best = np.argsort(-g, -1)[:, :2]
    for t in range(64):
        assert set(np.asarray(idx[t]) // 4) <= set(best[t])
        allowed = [e for e in range(16) if e // 4 in best[t]]
        top = sorted(allowed, key=lambda e: -sel[t, e])[:4]
        assert sorted(np.asarray(idx[t]).tolist()) == sorted(top)
    w = np.take_along_axis(np.asarray(s), np.asarray(idx), -1)
    np.testing.assert_allclose(np.asarray(gate), 2.5 * w / w.sum(-1, keepdims=True),
                               rtol=1e-5)


def test_expert_shares_sum_to_the_uncut_layer():
    """Over all shares of 4 experts, the routed parts plus the shared
    expert counted once equal the uncut drop-free layer, and the uncut
    drop-free layer equals the capacity path without drops."""
    from repro.models.moe import apply_moe_dropless, route
    cfg = v3_cfg(moe_capacity_factor=16.0)
    p = _v3_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.d_model))
    whole, stats = apply_moe_dropless(p, x, cfg)
    assert int(stats[0]) == int(stats[1]) == 2 * 16 * 4
    cap, _ = apply_moe(p, x, cfg)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(cap), atol=1e-4)
    shared = apply_mlp(p["shared"], x.reshape(-1, cfg.d_model),
                       dataclasses.replace(cfg, d_ff=cfg.moe_d_ff)
                       ).reshape(x.shape)
    total, held = jnp.zeros_like(x), 0
    for first in range(0, 16, 4):
        sc = dataclasses.replace(cfg, experts_held=4, first_held_expert=first)
        ps = {**p, **{k: p[k][first:first + 4]
                      for k in ("w_gate", "w_up", "w_down")}}
        y, st = apply_moe_dropless(ps, x, sc)
        total = total + (y - shared)
        held += int(st[0])
        assert int(st[1]) == 2 * 16 * 4
        idx = np.asarray(route(p, x.reshape(-1, cfg.d_model), cfg)[1])
        got = np.bincount(idx.ravel(), minlength=16)[first:first + 4]
        assert int(st[2]) == got.max() and int(st[3]) == (got > 0).sum()
    assert held == 2 * 16 * 4
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole),
                               atol=1e-4)


def test_dropless_output_ignores_batch_neighbours():
    """A token's drop-free MoE output is the same whatever the other rows
    hold, masked rows route nowhere, and a share of 4 of 16 experts counts
    only the pairs routed to it."""
    from repro.models.moe import apply_moe_dropless
    cfg = v3_cfg(experts_held=4, first_held_expert=8)
    p = _v3_params(cfg)
    p = {**p, **{k: p[k][:4] for k in ("w_gate", "w_up", "w_down")}}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 32, cfg.d_model))
    other = jax.random.normal(jax.random.PRNGKey(6), (1, 32, cfg.d_model)) * 3
    y1, _ = apply_moe_dropless(p, x, cfg)
    for mix in (other.at[:, 0].set(x[:, 0]),
                jnp.broadcast_to(x[:, :1], x.shape)):   # every row the same
        y2, _ = apply_moe_dropless(p, mix, cfg)
        np.testing.assert_allclose(np.asarray(y2[:, 0]), np.asarray(y1[:, 0]),
                                   atol=1e-5)
    mask = jnp.arange(32)[None] < 10
    ym, st = apply_moe_dropless(p, x, cfg, mask)
    np.testing.assert_allclose(np.asarray(ym[:, :10]), np.asarray(y1[:, :10]),
                               atol=1e-5)
    assert int(st[1]) == 10 * 4 and 0 <= int(st[0]) <= 10 * 4


def test_grouped_matmul_rows_and_the_unwritten_rest(monkeypatch):
    """The grouped product gives each held row its group's matrix (layer
    1 of a stack, read through LayerOf); the rows of no held expert, which
    the kernel never writes, cannot reach the layer's output."""
    from repro.kernels.grouped_matmul import ops as G
    from repro.models.layers import LayerOf
    from repro.models.moe import apply_moe_dropless
    lhs = jax.random.normal(jax.random.PRNGKey(11), (40, 128))
    rhs = jax.random.normal(jax.random.PRNGKey(12), (2, 3, 128, 256))
    sizes = jnp.asarray([5, 0, 9, 26], jnp.int32)
    out = G.grouped_matmul(lhs, LayerOf(rhs, 1), sizes)
    gid = np.repeat([0, 1, 2], [5, 0, 9])
    want = np.einsum("mk,mkn->mn", np.asarray(lhs[:14]),
                     np.asarray(rhs[1])[gid])
    np.testing.assert_allclose(np.asarray(out[:14]), want, rtol=1e-4,
                               atol=1e-3)

    cfg = v3_cfg(experts_held=4, first_held_expert=4)
    p = _v3_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(13), (2, 8, cfg.d_model))
    y, _ = apply_moe_dropless(p, x, cfg)
    real = G.grouped_matmul

    def nan_rest(lhs, rhs, group_sizes, **kw):
        got = real(lhs, rhs, group_sizes, **kw)
        held = jnp.sum(group_sizes[:-1])
        return jnp.where(jnp.arange(got.shape[0])[:, None] < held, got,
                         jnp.nan)

    monkeypatch.setattr(G, "grouped_matmul", nan_rest)
    y_nan, _ = apply_moe_dropless(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y_nan), np.asarray(y), atol=1e-6)
