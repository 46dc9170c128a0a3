"""Quantized KV cache: int8 pool leaves with per-(token, head) scales, the
dequantizing paged decode kernels, model-level greedy parity, and engine
serving with kv_dtype="int8"."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, get_smoke_config
from repro.kernels.flash_attention.ops import paged_decode_blocktable
from repro.kernels.flash_attention.ref import paged_decode_blocktable_ref
from repro.models import apply_lm, init_caches, init_lm
from repro.models.blocks import KV_DTYPES, kv_cache_dtype
from repro.quant import dequantize_kv, kv_bytes_per_token, quantize_kv
from repro.serving.engine import Engine, synthetic_requests
from repro.serving.serve_step import greedy_generate

KEY = jax.random.PRNGKey(3)


@pytest.fixture(scope="module")
def smoke_lm():
    cfg = get_smoke_config("internlm2-1.8b")
    return cfg, init_lm(jax.random.PRNGKey(0), cfg)


# -- quantize_kv / cache structure -------------------------------------------

class TestQuantizeKV:
    def test_round_trip(self):
        x = jax.random.normal(KEY, (2, 16, 4, 32))  # (b, s, nkv, d)
        q, scale = quantize_kv(x)
        assert q.dtype == jnp.int8 and q.shape == x.shape
        assert scale.dtype == jnp.float32 and scale.shape == (2, 16, 4)
        back = dequantize_kv(q, scale, jnp.float32)
        # per-(token, head) absmax: half a step of that slice's range
        step = np.asarray(scale).max()
        np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                                   atol=0.51 * step)

    def test_kv_cache_dtype_resolution(self):
        cfg = get_smoke_config("internlm2-1.8b")
        assert kv_cache_dtype(cfg, jnp.bfloat16) == jnp.bfloat16
        cfg8 = dataclasses.replace(cfg, kv_dtype="int8")
        assert kv_cache_dtype(cfg8, jnp.bfloat16) == jnp.int8
        bad = dataclasses.replace(cfg, kv_dtype="int4")
        with pytest.raises(ValueError, match="unknown kv_dtype 'int4'"):
            kv_cache_dtype(bad, jnp.bfloat16)
        assert "int8" in KV_DTYPES and "auto" in KV_DTYPES

    def test_mla_rejects_int8(self):
        cfg = get_smoke_config("deepseek-v3-671b")  # MLA attention
        assert cfg.attn_type == "mla"
        cfg8 = dataclasses.replace(cfg, kv_dtype="int8")
        with pytest.raises(ValueError, match="mla"):
            kv_cache_dtype(cfg8, jnp.bfloat16)

    def test_int8_cache_leaves(self):
        cfg = dataclasses.replace(get_smoke_config("internlm2-1.8b"),
                                  kv_dtype="int8")
        b, s_max = 2, 16
        caches = init_caches(cfg, b, s_max, jnp.float32)
        seg = caches[0]
        n = seg["k"].shape[0]
        assert seg["k"].dtype == jnp.int8 and seg["v"].dtype == jnp.int8
        assert seg["k_scale"].dtype == jnp.float32
        assert seg["k_scale"].shape == (n, b, s_max, cfg.num_kv_heads)
        assert seg["v_scale"].shape == seg["k_scale"].shape

    def test_kv_bytes_halve_pool_cost(self):
        # full-size config: at real head_dims the per-(token, head) scale
        # overhead is small next to the payload halving
        cfg = get_config("internlm2-1.8b")
        d = cfg.d_model // cfg.num_heads
        bf16 = kv_bytes_per_token(cfg.num_kv_heads, d)
        int8 = kv_bytes_per_token(cfg.num_kv_heads, d, "int8")
        # slots-per-GiB scales by the inverse ratio; scale overhead keeps it
        # just under the ideal 2x
        gib = 1 << 30
        slots_bf16 = gib // (bf16 * cfg.num_layers * 128)
        slots_int8 = gib // (int8 * cfg.num_layers * 128)
        assert 1.7 < slots_int8 / slots_bf16 <= 2.0


# -- dequantizing paged kernels ----------------------------------------------

def _quant_pools(nb, bs, nkv, d):
    kp = jax.random.normal(KEY, (nb, bs, nkv, d)) * 0.5
    vp = jax.random.normal(jax.random.fold_in(KEY, 1),
                           (nb, bs, nkv, d)) * 0.5
    kq, ks = quantize_kv(kp)
    vq, vs = quantize_kv(vp)
    return (kq, ks, vq, vs,
            dequantize_kv(kq, ks, jnp.float32),
            dequantize_kv(vq, vs, jnp.float32))


class TestQuantizedPagedDecode:
    # block_kv=64 is a chunk of two blocks: length 100 crosses a chunk
    # boundary and 128 fills two chunks; 96 is a chunk of three blocks, so
    # the last chunk of a 4-block table is partial; None takes the whole
    # table at once
    @pytest.mark.parametrize("use_pallas,block_kv", [
        pytest.param(True, None, id="True"),
        pytest.param(False, None, id="False"),
        pytest.param(True, 64, id="True-chunk_boundary"),
        pytest.param(True, 96, id="True-partial_chunk")])
    def test_blocktable_variant_vs_dequantized_ref(self, use_pallas,
                                                   block_kv):
        nb, bs, nkv, d, b, max_blocks = 12, 32, 2, 32, 3, 4
        kq, ks, vq, vs, kd, vd = _quant_pools(nb, bs, nkv, d)
        q = jax.random.normal(jax.random.fold_in(KEY, 3), (b, nkv * 2, d))
        tables = jnp.asarray([[3, 7, 1, 0], [11, 0, 0, 0], [2, 4, 6, 8]],
                             jnp.int32)
        lengths = jnp.asarray([100, 20, 128], jnp.int32)
        got = paged_decode_blocktable(q, kq, vq, tables, lengths, k_scale=ks,
                                      v_scale=vs, block_kv=block_kv,
                                      interpret=True, use_pallas=use_pallas)
        want = paged_decode_blocktable_ref(q, kd, vd, tables, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)

    @pytest.mark.parametrize("use_pallas", [True, False])
    def test_blocktable_dead_row_permuted_vs_dequantized_ref(self,
                                                             use_pallas):
        """A dead row (length 0) among rows whose tables name the pool's
        blocks out of order, one block shared by two rows."""
        nb, bs, nkv, d, b = 12, 32, 2, 32, 4
        kq, ks, vq, vs, kd, vd = _quant_pools(nb, bs, nkv, d)
        q = jax.random.normal(jax.random.fold_in(KEY, 4), (b, nkv * 3, d))
        tables = jnp.asarray([[9, 2, 5, 11], [0, 0, 0, 0], [6, 10, 1, 3],
                              [2, 8, 7, 4]], jnp.int32)
        lengths = jnp.asarray([97, 0, 128, 33], jnp.int32)   # 0 = dead row
        got = np.asarray(paged_decode_blocktable(
            q, kq, vq, tables, lengths, k_scale=ks, v_scale=vs,
            interpret=True, use_pallas=use_pallas))
        want = paged_decode_blocktable_ref(q, kd, vd, tables, lengths)
        np.testing.assert_allclose(got, np.asarray(want), atol=3e-5,
                                   rtol=3e-5)
        assert np.all(got[1] == 0.0)        # dead row stays zero

    def test_quant_close_to_float_pool(self):
        """End-to-end quantization noise on attention outputs stays small."""
        nb, bs, nkv, d, b = 8, 16, 2, 32, 2
        kq, ks, vq, vs, _, _ = _quant_pools(nb, bs, nkv, d)
        kp = jax.random.normal(KEY, (nb, bs, nkv, d)) * 0.5
        vp = jax.random.normal(jax.random.fold_in(KEY, 1),
                               (nb, bs, nkv, d)) * 0.5
        q = jax.random.normal(jax.random.fold_in(KEY, 2), (b, nkv, d))
        tables = jnp.asarray([[0, 1, 2, 3], [7, 6, 0, 0]], jnp.int32)
        lens = jnp.asarray([64, 32], jnp.int32)
        got = np.asarray(paged_decode_blocktable(
            q, kq, vq, tables, lens, k_scale=ks, v_scale=vs, interpret=True))
        want = np.asarray(paged_decode_blocktable_ref(q, kp, vp, tables,
                                                      lens))
        rel = np.abs(got - want).max() / np.abs(want).max()
        assert rel < 0.05


def _layer_pools(kv_dtype, layers, nb, bs, nkv, d):
    """(k, v, k_scale, v_scale) of each layer, a distinct pool a layer:
    int8 with its scales, or bf16 with none."""
    out = []
    for i in range(layers):
        kv = jax.random.normal(jax.random.fold_in(KEY, 10 + i),
                               (2, nb, bs, nkv, d)) * 0.5
        if kv_dtype == "int8":
            (kq, ks), (vq, vs) = quantize_kv(kv[0]), quantize_kv(kv[1])
            out.append((kq, vq, ks, vs))
        else:
            kv = kv.astype(jnp.bfloat16)
            out.append((kv[0], kv[1], None, None))
    return out


class TestLayerStackPagedDecode:
    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
    @pytest.mark.parametrize("use_pallas", [True, False])
    def test_layer_of_stack_vs_layer_and_ref(self, kv_dtype, use_pallas):
        """The kernel on `LayerOf(stack, i)` reads layer i of a 3-layer
        stack in place: the same output as on the sliced stack[i] and
        within the reference's tolerance of it, a dead row zero."""
        from repro.models.layers import LayerOf
        layers, nb, bs, nkv, d, b = 3, 12, 32, 2, 32, 4
        per_layer = _layer_pools(kv_dtype, layers, nb, bs, nkv, d)
        stacks = [None if per_layer[0][n] is None
                  else jnp.stack([p[n] for p in per_layer])
                  for n in range(4)]
        q = jax.random.normal(jax.random.fold_in(KEY, 5), (b, nkv * 2, d))
        tables = jnp.asarray([[9, 2, 5, 11], [0, 0, 0, 0], [6, 10, 1, 3],
                              [2, 8, 7, 4]], jnp.int32)
        lengths = jnp.asarray([97, 0, 128, 33], jnp.int32)   # 0 = dead row
        for i in range(layers):
            kb, vb, ks, vs = per_layer[i]
            at = [None if x is None else LayerOf(x, jnp.int32(i))
                  for x in stacks]
            got = np.asarray(paged_decode_blocktable(
                q, at[0], at[1], tables, lengths, k_scale=at[2],
                v_scale=at[3], interpret=True, use_pallas=use_pallas))
            one = np.asarray(paged_decode_blocktable(
                q, kb, vb, tables, lengths, k_scale=ks, v_scale=vs,
                interpret=True, use_pallas=use_pallas))
            if ks is not None:
                kb = dequantize_kv(kb, ks, jnp.float32)
                vb = dequantize_kv(vb, vs, jnp.float32)
            want = paged_decode_blocktable_ref(
                q, kb.astype(jnp.float32), vb.astype(jnp.float32), tables,
                lengths)
            np.testing.assert_array_equal(got, one)
            np.testing.assert_allclose(got, np.asarray(want), atol=3e-5,
                                       rtol=3e-5)
            assert np.all(got[1] == 0.0)        # dead row stays zero


# -- model-level greedy parity -----------------------------------------------

def _greedy(params, cfg, toks, n_new):
    b, s = toks.shape
    caches = init_caches(cfg, b, s + n_new, jnp.float32)
    logits, caches, _ = apply_lm(params, toks, cfg, caches=caches,
                                 cache_index=0)
    out = []
    nxt = jnp.argmax(logits[:, -1], axis=-1)
    for i in range(n_new):
        out.append(nxt)
        logits, caches, _ = apply_lm(params, nxt[:, None], cfg,
                                     caches=caches, cache_index=s + i,
                                     decode=True)
        nxt = jnp.argmax(logits[:, -1], axis=-1)
    return np.stack([np.asarray(t) for t in out], axis=1)


class TestModelGreedyParity:
    def test_int8_kv_tracks_f32_kv(self, smoke_lm):
        """A random-init model has near-uniform logits, so token-exact greedy
        parity over a long horizon is not a meaningful bar — what must hold
        is that the quantized cache perturbs logits only at quantization-noise
        scale, and the leading greedy tokens (before noise-level ties can
        flip) agree exactly."""
        cfg, params = smoke_lm
        cfg8 = dataclasses.replace(cfg, kv_dtype="int8")
        toks = jax.random.randint(KEY, (2, 12), 0, cfg.vocab_size)
        b, s = toks.shape

        # prefill logits under both caches: quantization-noise-level delta
        want_lg, _, _ = apply_lm(params, toks, cfg,
                                 caches=init_caches(cfg, b, s, jnp.float32),
                                 cache_index=0)
        got_lg, _, _ = apply_lm(params, toks, cfg8,
                                caches=init_caches(cfg8, b, s, jnp.float32),
                                cache_index=0)
        want_lg = np.asarray(want_lg, np.float32)
        got_lg = np.asarray(got_lg, np.float32)
        assert np.abs(got_lg - want_lg).max() / np.abs(want_lg).max() < 0.05

        want = _greedy(params, cfg, toks, 8)
        got = _greedy(params, cfg8, toks, 8)
        np.testing.assert_array_equal(got[:, :3], want[:, :3])


# -- engine serving with kv_dtype="int8" -------------------------------------

class TestEngineInt8KV:
    def _check(self, cfg8, params, reqs, done):
        assert [c.rid for c in done] == [r.rid for r in reqs]
        for r, c in zip(reqs, done):
            want = np.asarray(greedy_generate(
                params, cfg8, jnp.asarray(r.tokens[None]),
                r.max_new_tokens))[0]
            assert np.array_equal(np.asarray(c.tokens), want), f"rid {r.rid}"

    def test_token_parity(self, smoke_lm):
        cfg, params = smoke_lm
        reqs = synthetic_requests(6, pattern="burst", min_prompt=4,
                                  max_prompt=24, min_new=3, max_new=8,
                                  vocab=cfg.vocab_size, seed=21)
        eng = Engine(params, cfg, max_batch=4, max_prompt=32, max_new=8,
                     kv_dtype="int8")
        assert eng.cfg.kv_dtype == "int8"
        done, stats = eng.run(reqs)
        assert stats.prefills == 6
        # reference loop under the SAME quantized-cache config: continuous
        # batching + int8 pool reuse must not change a single token
        self._check(eng.cfg, params, reqs, done)

    def test_token_parity_paged_kernel(self, smoke_lm):
        cfg, params = smoke_lm
        reqs = synthetic_requests(4, pattern="burst", min_prompt=4,
                                  max_prompt=20, min_new=3, max_new=6,
                                  vocab=cfg.vocab_size, seed=23)
        eng = Engine(params, cfg, max_batch=4, max_prompt=32, max_new=8,
                     kv_dtype="int8")
        assert eng.cfg.attn_impl == "paged" and eng.cfg.kv_dtype == "int8"
        done, _ = eng.run(reqs)
        self._check(eng.cfg, params, reqs, done)

    def test_unknown_kv_dtype_raises(self, smoke_lm):
        cfg, params = smoke_lm
        with pytest.raises(ValueError, match="unknown kv_dtype 'fp4'"):
            Engine(params, cfg, max_batch=2, max_prompt=16, max_new=4,
                   kv_dtype="fp4")
