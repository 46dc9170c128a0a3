"""Autotuning subsystem: candidate lattice, cache round-trip, tuned kernel
dispatch, and measurement-calibrated advisor predictions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import advisor
from repro.core.gemm_model import GEMM, MeasuredProfile, estimate
from repro.core.hardware import get_hardware
from repro.configs.base import ModelConfig
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.matmul.ops import matmul
from repro.kernels.matmul.ref import matmul_ref
from repro.tuning import (TunedConfig, TuningCache, flash_candidates,
                          flash_vmem_bytes, matmul_candidates,
                          matmul_vmem_bytes, set_default_cache)
from repro.tuning.candidates import lane_granule, sublane_granule
from repro.tuning.search import autotune_flash_attention, autotune_matmul

HW = get_hardware("tpu_v5e")


@pytest.fixture(autouse=True)
def _reset_default_cache():
    yield
    set_default_cache(None)


class TestCandidateLattice:
    @pytest.mark.parametrize("m,k,n,dtype_bytes", [
        (256, 256, 256, 4), (512, 1024, 512, 2), (200, 80, 72, 2),
        (4096, 4096, 4096, 2), (64, 64, 64, 4),
    ])
    def test_matmul_candidates_aligned_and_within_vmem(self, m, k, n, dtype_bytes):
        cands = matmul_candidates(m, k, n, HW, dtype_bytes)
        assert cands, "lattice must never be empty"
        sub, lane = sublane_granule(HW, dtype_bytes), lane_granule(HW)
        for bm, bn, bk in cands:
            assert bm % sub == 0, (bm, sub)
            assert bn % lane == 0 and bk % lane == 0
            assert matmul_vmem_bytes(bm, bn, bk, dtype_bytes) <= HW.sram_bytes

    def test_matmul_default_always_present(self):
        assert (128, 128, 128) in matmul_candidates(4096, 4096, 4096, HW, 2)
        assert (128, 128, 128) in matmul_candidates(
            4096, 4096, 4096, HW, 2, max_candidates=3)

    @pytest.mark.parametrize("sq,skv,d", [(256, 256, 64), (1024, 2048, 128),
                                          (130, 130, 80)])
    def test_flash_candidates_aligned_and_within_vmem(self, sq, skv, d):
        cands = flash_candidates(sq, skv, d, HW, 2)
        assert cands
        sub, lane = sublane_granule(HW, 2), lane_granule(HW)
        for bq, bkv in cands:
            assert bq % sub == 0 and bkv % lane == 0
            assert flash_vmem_bytes(bq, bkv, d, 2) <= HW.sram_bytes

    def test_max_candidates_cap(self):
        cands = matmul_candidates(2048, 2048, 2048, HW, 2, max_candidates=5)
        assert len(cands) <= 5


class TestCacheRoundTrip:
    def _cfg(self):
        return TunedConfig(op="matmul", shape=(256, 512, 256), dtype="float32",
                           hw_name="tpu_v5e",
                           blocks={"block_m": 256, "block_n": 128, "block_k": 512},
                           time_us=123.4, baseline_us=246.8, candidates_tried=6)

    def test_save_load_identical(self, tmp_path):
        path = str(tmp_path / "cache.json")
        cache = TuningCache()
        cache.put(self._cfg())
        cache.save(path)
        loaded = TuningCache.load(path)
        assert len(loaded) == 1
        got = loaded.get("matmul", (256, 512, 256), "float32", "tpu_v5e")
        assert got == self._cfg()
        assert got.speedup_vs_default == pytest.approx(2.0)

    def test_missing_file_is_empty(self, tmp_path):
        cache = TuningCache.load(str(tmp_path / "absent.json"))
        assert len(cache) == 0
        assert cache.get("matmul", (1, 1, 1), "float32", "tpu_v5e") is None

    def test_wrong_key_misses(self, tmp_path):
        cache = TuningCache()
        cache.put(self._cfg())
        assert cache.get("matmul", (256, 512, 256), "bfloat16", "tpu_v5e") is None
        assert cache.get("matmul", (256, 512, 256), "float32", "a100") is None


class TestTunedDispatch:
    def test_autotune_then_tuned_matmul_matches_ref(self, tmp_path):
        m, k, n = 128, 128, 128
        cache = TuningCache()
        cfg = autotune_matmul(m, k, n, dtype=jnp.float32, cache=cache,
                              iters=1, warmup=1, max_candidates=3)
        assert cfg.candidates_tried >= 1
        assert cache.get("matmul", (m, k, n), "float32", "tpu_v5e") == cfg
        path = str(tmp_path / "cache.json")
        cache.save(path)
        set_default_cache(path)
        a = jax.random.normal(jax.random.PRNGKey(0), (m, k))
        b = jax.random.normal(jax.random.PRNGKey(1), (k, n))
        got = matmul(a, b, tuned=True, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(matmul_ref(a, b)),
                                   atol=2e-4, rtol=2e-5)

    def test_tuned_matmul_uses_cached_blocks(self):
        # a non-default block config must be honored and stay correct
        cache = TuningCache()
        cache.put(TunedConfig(op="matmul", shape=(128, 256, 128),
                              dtype="float32", hw_name="tpu_v5e",
                              blocks={"block_m": 128, "block_n": 128,
                                      "block_k": 256},
                              time_us=1.0))
        set_default_cache(cache)
        a = jax.random.normal(jax.random.PRNGKey(2), (128, 256))
        b = jax.random.normal(jax.random.PRNGKey(3), (256, 128))
        got = matmul(a, b, tuned=True, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(matmul_ref(a, b)),
                                   atol=2e-4, rtol=2e-5)

    def test_tuned_cache_miss_keeps_defaults(self):
        set_default_cache(TuningCache())
        a = jax.random.normal(jax.random.PRNGKey(4), (64, 64))
        b = jax.random.normal(jax.random.PRNGKey(5), (64, 64))
        got = matmul(a, b, tuned=True, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(matmul_ref(a, b)),
                                   atol=2e-4, rtol=2e-5)

    def test_autotune_then_tuned_flash_matches_ref(self):
        b, s, heads, d = 1, 128, 2, 64
        cache = TuningCache()
        autotune_flash_attention(b, s, heads, d, cache=cache, iters=1,
                                 warmup=1, max_candidates=2)
        set_default_cache(cache)
        key = jax.random.PRNGKey(6)
        q = jax.random.normal(key, (b, s, heads, d))
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, heads, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, heads, d))
        got = flash_attention(q, k, v, tuned=True, interpret=True)
        want = flash_attention(q, k, v, use_pallas=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4, rtol=2e-4)

    def test_autotune_then_tuned_paged_decode_blocktable_matches_ref(self):
        from repro.kernels.flash_attention.ops import paged_decode_blocktable
        from repro.kernels.flash_attention.ref import \
            paged_decode_blocktable_ref
        from repro.tuning.search import autotune_paged_decode_blocktable
        b, rows, s_max, nkv, heads, d = 3, 4, 128, 2, 4, 32
        cache = TuningCache()
        pool = autotune_paged_decode_blocktable(
            b, rows, s_max, nkv, heads, d, cache=cache, iters=1, warmup=1,
            max_candidates=2)
        bs, bkv = pool.blocks["block_size"], pool.blocks["block_kv"]
        assert s_max % bs == 0 and bkv % bs == 0
        assert cache.get("paged_decode_blocktable_pool",
                         (b, rows, s_max, nkv, heads, d), "float32",
                         "tpu_v5e") == pool
        # the kernel-level entry for the winning page size, at its pool
        nb = rows * (s_max // bs)
        kernel = cache.get("paged_decode_blocktable",
                           (b, nb, bs, nkv, heads, d), "float32", "tpu_v5e")
        assert kernel is not None and kernel.blocks["block_kv"] == bkv
        set_default_cache(cache)
        key = jax.random.PRNGKey(8)
        q = jax.random.normal(key, (b, heads, d))
        kb = jax.random.normal(jax.random.fold_in(key, 1), (nb, bs, nkv, d))
        vb = jax.random.normal(jax.random.fold_in(key, 2), (nb, bs, nkv, d))
        tables = jnp.asarray(np.random.default_rng(4).permutation(nb)[
            :b * (s_max // bs)].reshape(b, -1), jnp.int32)
        lengths = jnp.asarray([5, 64, 128], jnp.int32)
        got = paged_decode_blocktable(q, kb, vb, tables, lengths, tuned=True,
                                      interpret=True)
        want = paged_decode_blocktable_ref(q, kb, vb, tables, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4, rtol=2e-4)


class TestMeasuredProfile:
    def _cache(self, time_us=100.0):
        cache = TuningCache()
        cache.put(TunedConfig(op="matmul", shape=(512, 512, 512),
                              dtype="bfloat16", hw_name="tpu_v5e",
                              blocks={"block_m": 512, "block_n": 512,
                                      "block_k": 512},
                              time_us=time_us, baseline_us=2 * time_us))
        return cache

    def test_exact_hit_uses_measured_time(self):
        prof = MeasuredProfile.from_cache(self._cache(), "tpu_v5e")
        e = estimate(GEMM("g", 512, 512, 512), profile=prof)
        assert e.bound == "measured"
        assert e.time_s == pytest.approx(100e-6)
        # batch and count scale the per-call measurement
        e4 = estimate(GEMM("g", 512, 512, 512, batch=2, count=2), profile=prof)
        assert e4.time_s == pytest.approx(400e-6)

    def test_miss_is_calibrated_analytic(self):
        prof = MeasuredProfile.from_cache(self._cache(), "tpu_v5e")
        g = GEMM("g", 300, 300, 300)
        analytic = estimate(g).time_s
        blended = estimate(g, profile=prof).time_s
        assert blended == pytest.approx(analytic * prof.calibration)

    def test_empty_cache_gives_no_profile(self):
        assert MeasuredProfile.from_cache(TuningCache(), "tpu_v5e") is None

    def test_propose_uses_profile(self):
        cfg = ModelConfig(name="p", family="dense", num_layers=4, d_model=2560,
                          num_heads=32, num_kv_heads=32, d_ff=10240,
                          vocab_size=50257, mlp_type="gelu")
        set_default_cache(self._cache())
        props = advisor.propose(cfg, microbatch=4)
        analytic = advisor.advise(cfg, microbatch=4)
        assert props and analytic
        # profile-grounded predictions still rank and stay positive
        assert all(p.predicted_speedup > 0 for p in props)
        # absolute step times differ under the profile's calibration
        prof = MeasuredProfile.from_cache(self._cache(), "tpu_v5e")
        assert prof.calibration != pytest.approx(1.0)
        t_cal = advisor.step_time(cfg, profile=prof)
        t_ana = advisor.step_time(cfg)
        assert t_cal == pytest.approx(t_ana * prof.calibration, rel=1e-6)

    def test_propose_without_cache_matches_advise(self):
        cfg = ModelConfig(name="p", family="dense", num_layers=2, d_model=1024,
                          num_heads=8, num_kv_heads=8, d_ff=4096,
                          vocab_size=32000, mlp_type="gelu")
        set_default_cache(TuningCache())
        props = advisor.propose(cfg)
        base = advisor.advise(cfg)
        assert [p.change for p in props] == [p.change for p in base]
        for a, b in zip(props, base):
            assert a.predicted_speedup == pytest.approx(b.predicted_speedup)
