"""Latent attention (deepseek-v3 MLA): YaRN rope, the paged MLA decode
kernel against the absorbed and decompressed forms, and the engine serving
an MLA + sigmoid-router MoE share through its latent block pool."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, get_smoke_config
from repro.kernels.flash_attention.ops import paged_mla_decode
from repro.kernels.flash_attention.ref import mla_decode_ref
from repro.models import apply_lm, init_caches, init_lm
from repro.models.attention import (_mla_decode, _mla_latent_q, _mla_prefill,
                                    init_mla, mla_cache_width,
                                    mla_softmax_scale)
from repro.models.layers import (LayerOf, rope_inv_freq, rope_magnitude,
                                 yarn_softmax_scale)

KEY = jax.random.PRNGKey(3)


def test_yarn_frequencies_match_the_closed_form():
    """deepseek-v3: dims that turn more than beta_fast=32 times over the
    original 4096 keep theta^(-2i/64), those under beta_slow=1 are divided
    by the factor 40, with a linear ramp between dims lo and hi."""
    cfg = get_config("deepseek-v3-671b")
    dim, theta = 64, 1e4
    inv = rope_inv_freq(cfg, dim)
    base = theta ** (-np.arange(0, dim, 2) / dim)

    def corr(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(theta))
    lo, hi = math.floor(corr(32)), math.ceil(corr(1))
    assert (lo, hi) == (10, 23)
    np.testing.assert_allclose(inv[:lo], base[:lo], rtol=1e-6)
    np.testing.assert_allclose(inv[hi:], base[hi:] / 40, rtol=1e-6)
    i = 15
    ramp = (i - lo) / (hi - lo)
    np.testing.assert_allclose(inv[i], base[i] * (1 - ramp) + base[i] / 40 * ramp,
                               rtol=1e-6)
    # mscale: (0.1 ln 40 + 1)^2 on the scores, 1 on cos and sin
    assert yarn_softmax_scale(cfg) == pytest.approx((0.1 * math.log(40) + 1) ** 2)
    assert yarn_softmax_scale(cfg) == pytest.approx(1.8738542, rel=1e-6)
    assert rope_magnitude(cfg) == 1.0
    plain = dataclasses.replace(cfg, rope_yarn_factor=0.0)
    np.testing.assert_allclose(rope_inv_freq(plain, dim), base, rtol=1e-6)
    assert yarn_softmax_scale(plain) == 1.0
    assert mla_softmax_scale(cfg) == pytest.approx(1.8738542 / math.sqrt(192),
                                                   rel=1e-6)


def _mla_cfg():
    return dataclasses.replace(get_smoke_config("deepseek-v3-671b"),
                               dtype="float32")


def test_latent_cache_is_padded_to_whole_lanes():
    cfg = _mla_cfg()
    assert mla_cache_width(cfg) == 128
    assert mla_cache_width(get_config("deepseek-v3-671b")) == 640
    caches = init_caches(cfg, 2, 32, jnp.float32)
    assert caches[0]["latent"].shape[-1] == 128


@pytest.mark.parametrize("lengths", [[1, 16, 0, 37], [64, 5, 17, 48]])
def test_paged_mla_kernel_vs_absorbed_and_decompressed(lengths):
    """The Pallas kernel (interpret mode) over a shuffled latent block pool
    equals the absorbed XLA form over the contiguous latents, and the
    decoded attention output equals the decompressed form's."""
    cfg = _mla_cfg()
    p = init_mla(KEY, cfg)
    b, bs, max_blocks = len(lengths), 8, 8
    s_max = bs * max_blocks
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s_max, cfg.d_model))
    pos = jnp.arange(s_max)
    q_nope, q_rope, lat = _mla_latent_q(p, x, cfg, pos)
    lengths = jnp.asarray(lengths, jnp.int32)
    # the pool: every row's pages at shuffled physical blocks
    nb = b * max_blocks + 1
    perm = np.random.RandomState(0).permutation(nb - 1)
    tables = perm.reshape(b, max_blocks).astype(np.int32)
    pool = jnp.zeros((nb, bs, lat.shape[-1]), jnp.float32)
    pool = pool.at[tables.reshape(-1)].set(
        lat.reshape(b * max_blocks, bs, -1))
    # queries at each row's last live position
    last = jnp.maximum(lengths - 1, 0)
    qn = q_nope[jnp.arange(b), last][:, None]
    qr = q_rope[jnp.arange(b), last][:, None]
    kvr = cfg.kv_lora_rank
    wk = p["wk_up"].reshape(kvr, cfg.num_heads, cfg.qk_nope_dim)
    q_lat = jnp.einsum("ban,can->bac", qn[:, 0], wk)
    pad = jnp.zeros(q_lat.shape[:2] + (lat.shape[-1] - kvr - cfg.qk_rope_dim,))
    q = jnp.concatenate([q_lat, qr[:, 0], pad], -1)
    scale = mla_softmax_scale(cfg)
    got = paged_mla_decode(q, pool, jnp.asarray(tables), lengths, v_dim=kvr,
                           scale=scale, block_kv=16, interpret=True)
    want = mla_decode_ref(q, lat, lengths, v_dim=kvr, scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # the same pool as one layer of a stack, read in place
    stack = jnp.stack([jnp.zeros_like(pool), pool])
    got2 = paged_mla_decode(q, LayerOf(stack, jnp.int32(1)), jnp.asarray(tables),
                            lengths, v_dim=kvr, scale=scale, interpret=True)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # the whole absorbed decode (through W_uv and wo) against the
    # decompressed attention of each live row
    out = _mla_decode(p, qn, qr, dataclasses.replace(cfg, attn_impl="paged"),
                      pool=pool, block_tables=jnp.asarray(tables),
                      lengths=lengths)
    for r in range(b):
        n = int(lengths[r])
        if n == 0:
            continue
        dec = _mla_prefill(p, qn[r:r + 1], qr[r:r + 1], lat[r:r + 1, :n], cfg,
                           q_pos=jnp.asarray([n - 1]))
        from repro.models.attention import _mla_out
        np.testing.assert_allclose(np.asarray(out[r]),
                                   np.asarray(_mla_out(p, dec, cfg)[0]),
                                   atol=1e-4, rtol=1e-4)


def test_prefill_chunks_queries_and_matches_one_chunk():
    """The decompressed prefill takes queries a chunk at a time; the result
    does not depend on the chunk."""
    import repro.models.attention as A
    cfg = _mla_cfg()
    p = init_mla(KEY, cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 48, cfg.d_model))
    pos = jnp.arange(48)
    qn, qr, lat = _mla_latent_q(p, x, cfg, pos)
    assert A._query_chunk(2048, 128, 4096) == 64       # deepseek-v3 bucket
    chunked = _mla_prefill(p, qn, qr, lat, cfg, q_pos=pos)
    orig = A._query_chunk
    try:
        A._query_chunk = lambda s, a, skv: s
        whole = _mla_prefill(p, qn, qr, lat, cfg, q_pos=pos)
    finally:
        A._query_chunk = orig
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(whole),
                               atol=1e-5, rtol=1e-5)


def test_engine_serves_mla_moe_share_like_the_full_forward():
    """The engine's latent block pool and paged kernel, a share of 4 of 8:
    every served token is the greedy token of the full (uncached) forward
    of the same share over the prompt and the tokens served before it."""
    from repro.serving.engine import Engine
    from repro.serving.engine.request import Request
    cfg = dataclasses.replace(_mla_cfg(), experts_held=4, first_held_expert=2)
    params = init_lm(KEY, cfg)
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i, tokens=rng.randint(0, cfg.vocab_size, n).astype(
        np.int32), max_new_tokens=5) for i, n in enumerate([5, 17, 30])]
    eng = Engine(params, cfg, max_batch=4, max_prompt=32, max_new=8)
    done, _ = eng.run(reqs)
    assert eng.cfg.moe_dispatch == "dropless"
    full = dataclasses.replace(cfg, moe_dispatch="dropless")
    for r, c in zip(reqs, done):
        assert c.ok and len(c.tokens) == 5
        seq = np.concatenate([r.tokens, np.asarray(c.tokens[:-1], np.int32)])
        lg = apply_lm(params, jnp.asarray(seq[None]), full)[0][0]
        want = np.asarray(jnp.argmax(lg[len(r.tokens) - 1:], -1))
        np.testing.assert_array_equal(want, c.tokens)
    counts = eng.moe_counts
    assert 0 < counts["moe.routed_held"] < counts["moe.routed_total"]
    # a held expert with a row, at most all 4 of each MoE layer per step
    assert 0 < counts["moe.held_active"] <= min(
        counts["moe.routed_held"], 2 * 4 * eng.decode_steps)
    assert counts["moe.prefill_routed_total"] == \
        2 * cfg.top_k * sum(len(r.tokens) for r in reqs)   # 2 MoE layers


def test_engine_rejects_int8_latent_cache():
    from repro.serving.engine import Engine
    cfg = _mla_cfg()
    with pytest.raises(ValueError, match="mla"):
        Engine(init_lm(KEY, cfg), cfg, max_batch=2, max_prompt=16,
               max_new=4, kv_dtype="int8")
