"""Serving engine: token parity vs greedy_generate, the one block-table
pool (prefix_cache / use_paged_kernel False refused, per-row cache writes
only through block tables), bucket policy invariants, scheduler behavior."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.models import apply_lm, init_caches, init_lm
from repro.serving.engine import (Engine, Request, RequestQueue,
                                  SamplingParams, make_policy,
                                  synthetic_requests)
from repro.serving.serve_step import greedy_generate


@pytest.fixture(scope="module")
def smoke_lm():
    cfg = get_smoke_config("internlm2-1.8b")
    return cfg, init_lm(jax.random.PRNGKey(0), cfg)


class TestOnePool:
    """The engine serves from one block-table pool through one paged
    decode kernel: prefix_cache=False and use_paged_kernel=False are
    refused, and a per-row cache write needs block tables."""

    @pytest.mark.parametrize("flag", ["prefix_cache", "use_paged_kernel"])
    def test_engine_refuses_removed_paths(self, smoke_lm, flag):
        cfg, params = smoke_lm
        with pytest.raises(ValueError, match="one block-table KV pool"):
            Engine(params, cfg, max_batch=2, max_prompt=16, max_new=4,
                   **{flag: False})
        eng = Engine(params, cfg, max_batch=2, max_prompt=16, max_new=4,
                     **{flag: True})
        assert eng.cfg.attn_impl == "paged"

    def test_vector_cache_index_needs_block_tables(self):
        """A (b,) cache_index over a contiguous cache: GQA and MLA both
        refuse it without block tables, and keep the scalar write."""
        for arch in ("internlm2-1.8b", "deepseek-v3-671b"):
            cfg = get_smoke_config(arch)
            if cfg.num_experts:
                cfg = dataclasses.replace(cfg, moe_dispatch="dropless")
            params = init_lm(jax.random.PRNGKey(0), cfg)
            caches = init_caches(cfg, 2, 16, jnp.float32)
            tok = jnp.ones((2, 1), jnp.int32)
            with pytest.raises(AssertionError, match="needs block_tables"):
                apply_lm(params, tok, cfg, caches=caches,
                         cache_index=jnp.asarray([3, 5], jnp.int32),
                         decode=True)
            # the scalar write (prefill, greedy_generate) still serves
            logits, _, _ = apply_lm(params, tok, cfg, caches=caches,
                                    cache_index=3, decode=True)
            assert np.isfinite(np.asarray(logits)).all()


class TestDecodeWrite:
    @pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
    def test_decode_step_writes_only_each_rows_token(self, smoke_lm,
                                                     kv_dtype):
        """One decode step over the stacked pool (bf16 or int8 with its
        scales) writes each live row's new entries at (layer i,
        table[b, ci // bs], ci % bs) of every leaf's stack, as a
        contiguous-cache decode of that row writes them at ci; every other
        entry stays bit-equal but the garbage block, which dead rows
        write."""
        from repro.serving.engine.engine import MOE_COUNTERS, _make_decode_bt
        cfg, params = smoke_lm
        eng = Engine(params, dataclasses.replace(cfg, dtype="bfloat16"),
                     max_batch=4, max_prompt=32, max_new=16,
                     kv_dtype=kv_dtype)
        pool = eng.pool
        bs, garbage, n = pool.block_size, pool.garbage, pool.num_rows
        rng = np.random.RandomState(5)

        def fill(leaf):
            if leaf.dtype == jnp.int8:
                return rng.randint(-127, 128, leaf.shape).astype(np.int8)
            if leaf.dtype == jnp.float32:       # int8 scales
                return rng.uniform(1e-3, 2e-2, leaf.shape).astype(np.float32)
            return rng.standard_normal(leaf.shape).astype(leaf.dtype)
        old = jax.tree.map(fill, pool.caches)
        pos = np.zeros(n, np.int32)
        pos[:3] = [5, 2 * bs, 3 * bs + 7]      # rows 3.. are dead
        tables = np.full((n, pool.max_blocks), garbage, np.int32)
        tables[:3] = rng.permutation(garbage)[:3 * pool.max_blocks].reshape(
            3, -1)
        tok = rng.randint(0, cfg.vocab_size, (n, 1)).astype(np.int32)
        _, new, _ = _make_decode_bt(eng.cfg)(
            eng.params, jnp.asarray(tok), jax.tree.map(jnp.asarray, old),
            jnp.asarray(pos), jnp.asarray(tables),
            jnp.zeros(len(MOE_COUNTERS), jnp.int32))
        live = [(b, tables[b, pos[b] // bs], pos[b] % bs) for b in range(3)]
        for o, w in zip(jax.tree.leaves(old), jax.tree.leaves(new)):
            w = np.asarray(w)
            keep = np.ones(o.shape[:3], bool)    # (layers, blocks, bs)
            keep[:, garbage] = False
            for _, blk, off in live:
                keep[:, blk, off] = False
            np.testing.assert_array_equal(w[keep], o[keep])
        # the written entries are the row's own: what a contiguous-cache
        # decode of the row at its depth writes at pos[b].  Layer 0's are
        # bit-equal; deeper layers' follow attention computed by the paged
        # kernel here and by the contiguous path there, so they agree to
        # within 3% of the layer's largest entry (1.7% at worst seen), where
        # a misplaced write reads another token's or random bytes
        for b, blk, off in live:
            contig = pool._gather(jax.tree.map(jnp.asarray, old),
                                  jnp.asarray(tables[b]))
            _, ref, _ = apply_lm(eng.params, jnp.asarray(tok[b:b + 1]),
                                 eng.cfg, caches=contig,
                                 cache_index=int(pos[b]), decode=True)
            for r, w in zip(jax.tree.leaves(ref), jax.tree.leaves(new)):
                got = np.asarray(w)[:, blk, off].astype(np.float32)
                want = np.asarray(r)[:, 0, pos[b]].astype(np.float32)
                np.testing.assert_array_equal(got[0], want[0])
                for g, r in zip(got[1:], want[1:]):
                    np.testing.assert_allclose(g, r, rtol=0,
                                               atol=0.03 * np.abs(r).max())


class TestBucketPolicy:
    def test_tile_aligned_and_bounded(self):
        cfg = get_smoke_config("internlm2-1.8b")
        pol = make_policy(cfg, max_batch=3, max_prompt=48, max_seq=96)
        # f32 smoke config on TPU lattice: sublane granule 8, lane 128
        assert pol.num_slots % 8 == 0 and pol.num_slots >= 3
        assert all(b % 8 == 0 for b in pol.prompt_buckets)
        assert pol.prompt_buckets[-1] >= 48  # lattice covers max_prompt
        assert pol.seq_max % 128 == 0 and pol.seq_max >= 96
        assert pol.num_programs == 1 + len(pol.prompt_buckets)
        # snapping: every prompt length maps to a bucket >= it
        for n in (1, 7, 8, 9, 33, 48):
            assert pol.prompt_bucket(n) >= n

    def test_oversized_prompt_rejected(self):
        cfg = get_smoke_config("internlm2-1.8b")
        pol = make_policy(cfg, max_batch=2, max_prompt=16)
        with pytest.raises(ValueError):
            pol.prompt_bucket(17)


class TestRequestQueue:
    def test_arrival_order_and_clock(self):
        reqs = [Request(rid=i, tokens=np.ones(4, np.int32), max_new_tokens=1,
                        arrival_s=t) for i, t in enumerate([0.3, 0.0, 0.1])]
        q = RequestQueue(reqs)
        assert q.pop_ready(0.0).rid == 1
        assert q.pop_ready(0.05) is None     # rid 2 arrives at 0.1
        assert q.pop_ready(0.2).rid == 2
        assert q.next_arrival_s() == 0.3
        assert q.pop_ready(1.0).rid == 0 and len(q) == 0

    def test_push_keeps_arrival_order(self):
        q = RequestQueue([Request(rid=0, tokens=np.ones(2, np.int32),
                                  max_new_tokens=1, arrival_s=10.0)])
        q.push(Request(rid=1, tokens=np.ones(2, np.int32),
                       max_new_tokens=1, arrival_s=0.0))
        assert q.next_arrival_s() == 0.0     # earlier arrival jumps ahead
        assert q.pop_ready(0.0).rid == 1


class TestEngineParity:
    """Continuous batching must not change what gets generated: engine
    outputs are token-identical to the reference greedy loop, per request,
    under mixed prompt lengths, staggered arrivals, and slot reuse."""

    def _check(self, cfg, params, reqs, done):
        assert [c.rid for c in done] == [r.rid for r in reqs]
        for r, c in zip(reqs, done):
            want = np.asarray(greedy_generate(
                params, cfg, jnp.asarray(r.tokens[None]),
                r.max_new_tokens))[0]
            assert np.array_equal(np.asarray(c.tokens), want), f"rid {r.rid}"

    def test_token_parity_with_queueing(self, smoke_lm):
        cfg, params = smoke_lm
        # 10 requests through an 8-slot pool: queueing + slot reuse
        reqs = synthetic_requests(10, pattern="burst", min_prompt=4,
                                  max_prompt=30, min_new=3, max_new=12,
                                  vocab=cfg.vocab_size, seed=3)
        eng = Engine(params, cfg, max_batch=4, max_prompt=32, max_new=16)
        done, stats = eng.run(reqs)
        assert stats.prefills == 10 and stats.total_generated == sum(
            r.max_new_tokens for r in reqs)
        self._check(cfg, params, reqs, done)

    def test_token_parity_staggered_arrivals(self, smoke_lm):
        cfg, params = smoke_lm
        reqs = synthetic_requests(6, pattern="uniform", min_prompt=4,
                                  max_prompt=24, min_new=3, max_new=8,
                                  vocab=cfg.vocab_size, step_s=2e-3, seed=9)
        assert reqs[-1].arrival_s > 0  # actually staggered
        eng = Engine(params, cfg, max_batch=4, max_prompt=32, max_new=8)
        done, _ = eng.run(reqs)
        self._check(cfg, params, reqs, done)

    def test_token_parity_paged_kernel(self, smoke_lm):
        cfg, params = smoke_lm
        reqs = synthetic_requests(5, pattern="burst", min_prompt=4,
                                  max_prompt=24, min_new=3, max_new=6,
                                  vocab=cfg.vocab_size, seed=11)
        eng = Engine(params, cfg, max_batch=4, max_prompt=32, max_new=8,
                     use_paged_kernel=True)
        assert eng.cfg.attn_impl == "paged"
        done, _ = eng.run(reqs)
        self._check(cfg, params, reqs, done)

    def test_static_policy_same_tokens_more_steps(self, smoke_lm):
        cfg, params = smoke_lm
        reqs = synthetic_requests(12, pattern="burst", min_prompt=4,
                                  max_prompt=24, min_new=2, max_new=10,
                                  vocab=cfg.vocab_size, seed=13)
        eng = Engine(params, cfg, max_batch=4, max_prompt=32, max_new=16)
        done_c, stats_c = eng.run(reqs, policy="continuous")
        done_s, stats_s = eng.run(reqs, policy="static")
        for a, b in zip(done_c, done_s):
            assert a.tokens == b.tokens
        # static drains the pool between batches: strictly more pool-wide
        # decode steps for the same tokens (the continuous-batching win)
        assert stats_s.decode_steps > stats_c.decode_steps

    def test_temperature_sampling_reproducible(self, smoke_lm):
        cfg, params = smoke_lm
        reqs = [Request(rid=i, tokens=np.arange(4 + i, dtype=np.int32) % 50,
                        max_new_tokens=5,
                        sampling=SamplingParams(temperature=0.8, seed=42 + i))
                for i in range(3)]
        eng = Engine(params, cfg, max_batch=4, max_prompt=16, max_new=8)
        d1, _ = eng.run(reqs)
        d2, _ = eng.run(reqs, policy="static")
        # per-request PRNG streams: same tokens regardless of scheduling
        for a, b in zip(d1, d2):
            assert a.tokens == b.tokens
        assert all(0 <= t < cfg.padded_vocab_size
                   for c in d1 for t in c.tokens)

    def test_slot_reuse_never_leaks_stale_kv(self, smoke_lm):
        """Regression: a released slot keeps its KV bytes (release only zeros
        the length); the next occupant must never attend the previous
        occupant's tokens.  Fill every slot deep, then re-occupy every slot
        shallow and decode past the prompt — any leak changes the tokens."""
        cfg, params = smoke_lm
        eng = Engine(params, cfg, max_batch=2, max_prompt=32, max_new=8)
        n = eng.policy.num_slots
        rng = np.random.RandomState(3)
        deep = [Request(rid=i, tokens=rng.randint(
                    0, cfg.vocab_size, size=28).astype(np.int32),
                    max_new_tokens=2) for i in range(n)]
        eng.run(deep)
        assert eng.pool.num_free == n
        assert all(l == 0 for l in eng.pool.lengths)
        shallow = [Request(rid=100 + i, tokens=rng.randint(
                       0, cfg.vocab_size, size=4).astype(np.int32),
                       max_new_tokens=6) for i in range(n)]
        done, _ = eng.run(shallow)
        self._check(cfg, params, shallow, done)

    def test_unsupported_family_rejected(self):
        cfg = get_smoke_config("mamba2-780m")
        with pytest.raises(NotImplementedError):
            Engine(params=None, cfg=cfg)

    def test_inadmissible_request_rejected_without_wedging(self, smoke_lm):
        """An inadmissible request becomes a rejected Completion — run()
        must not raise, leak a slot, or wedge subsequent service."""
        cfg, params = smoke_lm
        eng = Engine(params, cfg, max_batch=2, max_prompt=16, max_new=8)
        bad = [Request(rid=0, tokens=np.ones(8, np.int32),
                       max_new_tokens=eng.policy.seq_max)]  # depth overflow
        done, stats = eng.run(bad)
        assert [c.finish_reason for c in done] == ["rejected"]
        assert done[0].tokens == [] and done[0].ttft_s is None
        assert stats.num_rejected == 1 and stats.num_ok == 0
        assert eng.pool.num_free == eng.policy.num_slots  # no slot leaked
        ok = [Request(rid=1, tokens=np.ones(8, np.int32), max_new_tokens=3)]
        done, _ = eng.run(ok)  # engine still serves after the rejection
        assert len(done) == 1 and len(done[0].tokens) == 3

    def test_calibrate_with_bucket_at_pool_edge(self, smoke_lm):
        cfg, params = smoke_lm
        # top bucket (128) lands exactly on lane alignment: warm prompts at
        # bucket width must still fit the pool's generation headroom
        eng = Engine(params, cfg, max_batch=2, max_prompt=124, max_new=4)
        assert eng.policy.seq_max >= eng.policy.prompt_buckets[-1] + 4
        assert eng.calibrate_step_s() > 0.0
