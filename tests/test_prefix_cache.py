"""Prefix caching + copy-on-write block sharing: serving-correctness suite.

Locks down the block-table KV pool (kv_pool.BlockPool / PagedPool) and the
prefix-cache engine path end to end:

  * block-table decode kernel vs the jnp oracle (permuted tables, dead rows,
    chunk-length sweep, lengths at page and chunk edges, pages past each
    row's live prefix never read);
  * the BlockPool host state machine — prefix-hit sharing, full-hit COW,
    LRU eviction, exhaustion rollback — plus a seeded random driver with
    shadow block contents that asserts COW never lets one sequence observe
    another's writes (runs even without hypothesis; the hypothesis twin
    lives in test_property.py);
  * the engine contract: greedy outputs through the block pool and its
    prefix cache are byte-identical to the reference loop
    (`serve_step.greedy_generate`), on an 80% shared / 20% cold workload,
    across divergence after a shared prefix, and under eviction pressure.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.kernels.flash_attention.ops import paged_decode_blocktable
from repro.kernels.flash_attention.ref import (gather_block_kv,
                                               paged_decode_blocktable_ref)
from repro.models import init_lm
from repro.serving.engine import (BlockPool, BucketPolicy, Engine, PagedPool,
                                  PoolExhausted, Request, synthetic_requests)
from repro.serving.serve_step import greedy_generate

KEY = jax.random.PRNGKey(11)


@pytest.fixture(scope="module")
def smoke_lm():
    cfg = get_smoke_config("internlm2-1.8b")
    return cfg, init_lm(jax.random.PRNGKey(0), cfg)


class TestBlockTableKernel:
    """Pallas block-table decode vs the jnp oracle: physical indirection,
    shared blocks across rows, dead rows, chunk lengths (`block_kv`, in
    tokens, rounded up to whole blocks) shorter and longer than a block."""

    def _inputs(self, b=5, nb=12, bs=16, nkv=2, g=3, d=32):
        q = jax.random.normal(KEY, (b, nkv * g, d)) * 0.5
        kp = jax.random.normal(jax.random.fold_in(KEY, 1),
                               (nb, bs, nkv, d)) * 0.5
        vp = jax.random.normal(jax.random.fold_in(KEY, 2),
                               (nb, bs, nkv, d)) * 0.5
        # permuted, partially *shared* tables (rows 0 and 2 share block 7)
        tables = jnp.asarray([[7, 3, 1, 0],
                              [2, 8, 9, 4],
                              [7, 5, 0, 0],
                              [10, 0, 0, 0],
                              [11, 6, 3, 2]], jnp.int32)
        lengths = jnp.asarray([50, 64, 17, 0, 33], jnp.int32)  # 0 = dead row
        return q, kp, vp, tables, lengths

    @pytest.mark.parametrize("block_kv", [None, 8, 16, 12, 32, 48])
    def test_vs_ref(self, block_kv):
        q, kp, vp, tables, lengths = self._inputs()
        # 8, 12 and 16 round up to a chunk of one block of 16; 32 is two
        # blocks a chunk; 48 is three, which do not divide the table's 4, so
        # the 50- and 64-token rows end on a partial chunk; None is the
        # default chunk, capped at the table's 4 blocks
        got = paged_decode_blocktable(q, kp, vp, tables, lengths,
                                      block_kv=block_kv, interpret=True)
        want = paged_decode_blocktable_ref(q, kp, vp, tables, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)

    # a chunk of 64 tokens is 4 blocks of 16; the table holds 12 (192 tokens)
    @pytest.mark.parametrize("length,poison", [
        (1, False), (16, False), (64, False), (65, False), (192, False),
        (65, True)], ids=["one_token", "one_block", "one_chunk",
                          "chunk_plus_one", "whole_table", "nan_past_live"])
    def test_lengths_vs_ref(self, length, poison):
        """Row 0 at `length`; row 1 at the same length shares row 0's first
        block; row 2 is dead; row 3 spans two chunks.  With `poison`, every
        block outside the rows' live prefixes -- the garbage block among
        them -- holds NaN: the output stays finite, so no such block is
        read."""
        nb, bs, nkv, g, d, max_blocks = 48, 16, 2, 3, 32, 12
        garbage = nb - 1
        q = jax.random.normal(KEY, (4, nkv * g, d)) * 0.5
        kp = jax.random.normal(jax.random.fold_in(KEY, 1),
                               (nb, bs, nkv, d)) * 0.5
        vp = jax.random.normal(jax.random.fold_in(KEY, 2),
                               (nb, bs, nkv, d)) * 0.5
        ids = np.random.default_rng(3).permutation(garbage)
        tables = np.stack([ids[:12],
                           np.concatenate([ids[:1], ids[12:23]]),
                           np.full(12, garbage),
                           ids[23:35]]).astype(np.int32)
        lengths = np.asarray([length, length, 0, 100], np.int32)
        want = paged_decode_blocktable_ref(q, kp, vp, jnp.asarray(tables),
                                           jnp.asarray(lengths))
        if poison:
            live = {int(tables[r, j]) for r in range(4)
                    for j in range(-(-int(lengths[r]) // bs))}
            dead = np.asarray([i for i in range(nb) if i not in live])
            kp = kp.at[dead].set(jnp.nan)
            vp = vp.at[dead].set(jnp.nan)
        got = np.asarray(paged_decode_blocktable(
            q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths),
            block_kv=64, interpret=True))
        assert np.isfinite(got).all()
        assert np.all(got[2] == 0.0)
        np.testing.assert_allclose(got, np.asarray(want), atol=3e-5,
                                   rtol=3e-5)

    def test_dead_row_is_zero(self):
        q, kp, vp, tables, lengths = self._inputs()
        out = np.asarray(paged_decode_blocktable(q, kp, vp, tables, lengths,
                                                 interpret=True))
        assert np.all(out[3] == 0.0)
        assert np.any(out[0] != 0.0)

    def test_ref_matches_contiguous_gather(self):
        """The oracle itself: gathering blocks into a contiguous view and
        attending there equals attending through the table."""
        q, kp, vp, tables, lengths = self._inputs()
        kc = gather_block_kv(kp, tables)
        vc = gather_block_kv(vp, tables)
        assert kc.shape == (5, 4 * 16, 2, 32)
        got = paged_decode_blocktable_ref(q, kp, vp, tables, lengths)
        # re-pose the gathered views as a pool of 1-token blocks with per-row
        # identity tables: the indirection must be invisible
        ident = (jnp.arange(64)[None] +
                 jnp.arange(5)[:, None] * 64).astype(jnp.int32)
        want = paged_decode_blocktable_ref(
            q, kc.reshape(5 * 64, 1, 2, 32), vc.reshape(5 * 64, 1, 2, 32),
            ident, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    def test_jnp_fallback_path(self):
        q, kp, vp, tables, lengths = self._inputs()
        got = paged_decode_blocktable(q, kp, vp, tables, lengths,
                                      use_pallas=False)
        want = paged_decode_blocktable_ref(q, kp, vp, tables, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6, rtol=1e-6)


class TestBlockPoolStateMachine:
    """Deterministic transitions of the pure-host block pool."""

    def test_prefix_hit_shares_full_blocks(self):
        pool = BlockPool(16, 4)
        toks = list(range(10))                     # 2 full blocks + tail
        a, cows = pool.alloc_sequence(toks)
        assert not cows and a.num_cached == 0 and len(a.table) == 3
        pool.commit(a, toks)
        b, cows = pool.alloc_sequence(toks[:8] + [99, 98])
        assert not cows
        assert b.num_cached == 8                   # both full blocks shared
        assert b.table[:2] == a.table[:2] and b.table[2] != a.table[2]
        assert pool.ref[a.table[0]] == 2 and pool.ref[a.table[1]] == 2
        pool.check()

    def test_partial_chain_match_stops_at_divergence(self):
        pool = BlockPool(16, 4)
        a, _ = pool.alloc_sequence(list(range(8)))
        pool.commit(a, list(range(8)))
        # same first block, different second: chained hash stops after one
        b, _ = pool.alloc_sequence([0, 1, 2, 3, 9, 9, 9, 9])
        assert b.num_cached == 4 and b.table[0] == a.table[0]
        # same *contents* in block 1 but different parent chain: no hit
        c, _ = pool.alloc_sequence([5, 5, 5, 5] + list(range(4, 8)))
        assert c.num_cached == 0
        pool.check()

    def test_full_hit_cow_forks_tail(self):
        pool = BlockPool(16, 4)
        toks = list(range(8))
        a, _ = pool.alloc_sequence(toks)
        pool.commit(a, toks)
        b, cows = pool.alloc_sequence(toks)        # identical prompt
        # the final token is recomputed into a private fork: the shared
        # original is never written
        assert b.num_cached == 7
        assert len(cows) == 1
        assert cows[0].src == a.table[1] and cows[0].dst == b.table[1]
        assert b.table[0] == a.table[0] and b.table[1] != a.table[1]
        assert pool.ref[b.table[1]] == 1 and pool.ref[a.table[1]] == 1
        pool.check()

    def test_release_keeps_cache_warm_then_lru_evicts(self):
        pool = BlockPool(4, 2)
        a, _ = pool.alloc_sequence([1, 2, 3, 4])
        pool.commit(a, [1, 2, 3, 4])
        pool.release(a)
        assert pool.num_cached_blocks == 2 and pool.num_free_blocks == 2
        b, _ = pool.alloc_sequence([1, 2, 3, 4, 5])   # warm: both blocks hit
        assert b.num_cached == 4
        pool.release(b)
        # 8 distinct tokens -> 4 fresh blocks: free list drains, then the
        # LRU cached-free blocks are evicted
        c, _ = pool.alloc_sequence([7, 8, 9, 10, 11, 12, 13, 14])
        assert pool.evictions >= 2
        pool.check()
        pool.release(c)
        d, _ = pool.alloc_sequence([1, 2, 3, 4, 5])   # cache was evicted
        assert d.num_cached == 0
        pool.check()

    def test_exhaustion_rolls_back_cleanly(self):
        pool = BlockPool(2, 2)
        a, _ = pool.alloc_sequence([1, 2, 3, 4])
        pool.commit(a, [1, 2, 3, 4])
        ref_before = list(pool.ref)
        with pytest.raises(PoolExhausted):
            # hits block [1,2] (ref++), then needs 2 fresh blocks: none left
            pool.alloc_sequence([1, 2, 5, 6, 7, 8])
        assert pool.ref == ref_before              # hit refs rolled back
        pool.check()
        # the pool still works after the failed admission
        pool.release(a)
        b, _ = pool.alloc_sequence([1, 2, 9, 10])
        assert b.num_cached == 2
        pool.check()

    def test_prepare_append_boundary_cow_and_unregister(self):
        pool = BlockPool(8, 2)
        a, _ = pool.alloc_sequence([1, 2, 3])      # blocks: [full, tail]
        pool.commit(a, [1, 2, 3])
        # decode-time divergence: fork shares every block; the forked tail
        # must be COW'd before either writer touches it
        b = pool.fork(a)
        assert pool.ref[a.table[1]] == 2
        cow = pool.prepare_append(b)
        assert cow is not None and cow.src == a.table[1]
        assert b.table[1] != a.table[1] and pool.ref[a.table[1]] == 1
        pool.advance(b)
        # a's tail is private again: appending needs no copy
        assert pool.prepare_append(a) is None
        pool.advance(a)
        # boundary: position 4 opens a fresh private block
        assert a.length == 4 and len(a.table) == 2
        assert pool.prepare_append(a) is None and len(a.table) == 3
        pool.check()

    def test_prepare_append_unregisters_written_tail(self):
        pool = BlockPool(8, 2)
        a, _ = pool.alloc_sequence([1, 2, 3])
        # speculative commit claims the tail block through token 4: a write
        # at position 3 would corrupt that cache entry, so prepare_append
        # must un-register it first
        pool.commit(a, [1, 2, 3, 4])
        assert pool.prepare_append(a) is None
        pool.advance(a)
        pool.check()
        b, _ = pool.alloc_sequence([1, 2, 3, 4])
        assert b.num_cached == 2                   # only block 0 still hits
        pool.check()

    @pytest.mark.parametrize("seed", range(6))
    def test_random_driver_shadow_contents(self, seed):
        """Seeded alloc/fork/append/release storm with shadow block contents:

        * every write lands in a block with refcount exactly 1 (COW);
        * prefix-hit blocks hold exactly the prompt's tokens;
        * at every step, every live sequence reads back its own tokens —
          no sequence ever observes another's writes;
        * pool.check() invariants hold after every transition, including
          after PoolExhausted rollbacks.
        """
        drive_block_pool(seed, steps=120, num_blocks=24, block_size=4)


def drive_block_pool(seed, *, steps, num_blocks, block_size):
    """The random state-machine driver (shared shape with the hypothesis
    interpreter in test_property.py)."""
    rng = np.random.RandomState(seed)
    bs = block_size
    pool = BlockPool(num_blocks, bs)
    mem = {b: [None] * bs for b in range(num_blocks)}   # shadow KV contents
    live = []                                           # (seq, tokens)
    vocab = 40
    prefixes = [rng.randint(0, vocab, size=bs * k).tolist() for k in (1, 2, 3)]

    def write(seq, pos, tok):
        blk = seq.table[pos // bs]
        assert pool.ref[blk] == 1, \
            f"seed {seed}: write to shared block {blk} (ref {pool.ref[blk]})"
        mem[blk][pos % bs] = tok

    def apply_cow(c):
        mem[c.dst] = list(mem[c.src])

    for step in range(steps):
        op = rng.choice(4, p=[0.4, 0.3, 0.1, 0.2])
        if op == 0:                                     # admit a prompt
            base = prefixes[rng.randint(3)] if rng.rand() < 0.7 else []
            tail = rng.randint(0, vocab,
                               size=rng.randint(1, 3 * bs)).tolist()
            tokens = base + tail
            try:
                seq, cows = pool.alloc_sequence(tokens)
            except PoolExhausted:
                pool.check()                            # rollback left it sane
                if live:
                    s, _ = live.pop(rng.randint(len(live)))
                    pool.release(s)
                continue
            for c in cows:
                apply_cow(c)
            p = seq.num_cached
            for j in range(p // bs):                    # hit content is right
                assert mem[seq.table[j]] == tokens[j * bs:(j + 1) * bs]
            for pos in range(p, len(tokens)):           # suffix prefill
                write(seq, pos, tokens[pos])
            pool.commit(seq, tokens)
            live.append((seq, list(tokens)))
        elif op == 1 and live:                          # one decode append
            seq, tokens = live[rng.randint(len(live))]
            try:
                c = pool.prepare_append(seq)
            except PoolExhausted:
                pool.check()
                continue
            if c is not None:
                apply_cow(c)
            tok = int(rng.randint(0, vocab))
            write(seq, seq.length, tok)
            pool.advance(seq)
            tokens.append(tok)
        elif op == 2 and live:                          # fork (divergence)
            seq, tokens = live[rng.randint(len(live))]
            live.append((pool.fork(seq), list(tokens)))
        elif op == 3 and live:                          # release
            s, _ = live.pop(rng.randint(len(live)))
            pool.release(s)
        pool.check()
        for seq, tokens in live:                        # isolation: each seq
            for pos in range(seq.length):               # reads its own tokens
                got = mem[seq.table[pos // bs]][pos % bs]
                assert got == tokens[pos], (seed, step, seq.sid, pos)
    return pool


class TestPagedPoolDevice:
    """PagedPool: the device mirror of the host state machine."""

    @pytest.fixture(scope="class")
    def tiny(self):
        cfg = get_smoke_config("internlm2-1.8b")
        return cfg

    def test_gather_scatter_roundtrip_respects_shared_blocks(self, tiny):
        pool = PagedPool(tiny, num_rows=2, seq_max=16, dtype=jnp.float32,
                         block_size=4)
        toks = list(range(8))
        seq = pool.alloc_sequence(0, toks)
        # cache leaves carry the scanned layer dim first: blocks are axis 1
        k0 = pool.caches[0]["k"]
        marked = k0.at[:, seq.table[0]].set(7.0).at[:, seq.table[1]].set(3.0)
        pool.caches[0]["k"] = marked
        pool.commit(0, toks)
        # second row shares block 0; scatter from its first private block
        # must leave the shared block untouched
        seq2 = pool.alloc_sequence(1, toks[:4] + [9, 9, 9, 9])
        assert seq2.num_cached == 4 and seq2.table[0] == seq.table[0]
        contig = pool.gather(1)
        np.testing.assert_allclose(
            np.asarray(contig[0]["k"][0, 0, :4]), 7.0)  # hit KV visible
        zeroed = jax.tree.map(jnp.zeros_like, contig)
        pool.scatter(1, zeroed, seq2.num_cached // pool.block_size)
        k = np.asarray(pool.caches[0]["k"])
        assert np.all(k[:, seq.table[0]] == 7.0)        # shared: untouched
        assert np.all(k[:, seq2.table[1]] == 0.0)       # private: rewritten
        assert np.all(k[:, seq.table[1]] == 3.0)        # other row: untouched
        pool.blocks.check()

    def test_row_release_realloc_never_leaks(self, tiny):
        pool = PagedPool(tiny, num_rows=2, seq_max=16, dtype=jnp.float32,
                         block_size=4)
        row = pool.alloc()
        pool.alloc_sequence(row, list(range(12)))
        pool.release(row)
        assert pool.lengths == [0, 0] and pool.num_free == 2
        row = pool.alloc()
        seq = pool.alloc_sequence(row, [50, 51])
        # fresh table, fresh length: nothing of the previous occupant remains
        assert seq.num_cached == 0 and pool.lengths[row] == 2
        tab = pool.tables()
        assert tab.shape == (2, 4)
        assert np.all(tab[1 - row] == pool.garbage)     # dead row: garbage
        assert np.all(tab[row, 1:] == pool.garbage)     # unallocated tail
        pool.blocks.check()


def _run_pair(cfg, params, reqs, *, policy=None, block_size=8, **kw):
    """Run the workload through the engine and each request through the
    reference greedy loop; assert byte-identical tokens; return the engine,
    its stats and its completions."""
    eng = Engine(params, cfg, policy=policy, block_size=block_size, **kw)
    done_p, stats = eng.run(reqs)
    assert [c.rid for c in done_p] == [r.rid for r in reqs]
    for r, c in zip(reqs, done_p):
        want = np.asarray(greedy_generate(
            params, cfg, jnp.asarray(r.tokens[None]), r.max_new_tokens))[0]
        assert c.tokens == want.tolist(), f"rid {r.rid}: cache changed tokens"
    eng.pool.blocks.check()
    assert eng.pool.num_free == eng.policy.num_slots    # all rows released
    return eng, stats, done_p


class TestPrefixCacheEngine:
    """The contract: prefix caching is invisible in the tokens."""

    def test_token_identity_shared_prefix_workload(self, smoke_lm):
        cfg, params = smoke_lm
        # 80% of requests share a 16-token system prefix (2 full blocks at
        # block_size=8); 20% are cold
        reqs = synthetic_requests(10, pattern="burst", min_prompt=20,
                                  max_prompt=30, min_new=3, max_new=8,
                                  vocab=cfg.vocab_size, prefix_share=0.8,
                                  shared_prefix_len=16, seed=5)
        eng, stats, done1 = _run_pair(cfg, params, reqs, max_batch=4,
                                      max_prompt=32, max_new=8)
        assert stats.cache_hit_requests >= 2
        assert stats.cached_tokens >= 16 * stats.cache_hit_requests
        assert 0.0 < stats.cache_hit_rate < 1.0
        assert stats.prompt_tokens == sum(r.prompt_len for r in reqs)
        # rerun on the warm cache: every previously-seen prompt now hits,
        # and the tokens still don't change
        done2, stats2 = eng.run(reqs)
        for a, b in zip(done1, done2):
            assert a.tokens == b.tokens, f"rid {a.rid}: warm rerun diverged"
        assert stats2.cache_hit_rate > stats.cache_hit_rate
        assert stats2.cache_hit_requests == len(reqs)
        eng.pool.blocks.check()

    def test_divergence_after_shared_prefix(self, smoke_lm):
        cfg, params = smoke_lm
        rng = np.random.RandomState(0)
        P = rng.randint(0, cfg.vocab_size, size=16).astype(np.int32)
        reqs = [
            Request(rid=0, tokens=np.concatenate(
                [P, np.asarray([3, 5, 7], np.int32)]), max_new_tokens=6),
            Request(rid=1, tokens=np.concatenate(
                [P, np.asarray([11, 13], np.int32)]), max_new_tokens=6),
            Request(rid=2, tokens=P.copy(), max_new_tokens=6),  # full hit
        ]
        eng = Engine(params, cfg, max_batch=4, max_prompt=32, max_new=8,
                     block_size=8)
        done, _ = eng.run(reqs)
        for r, c in zip(reqs, done):
            want = np.asarray(greedy_generate(
                params, cfg, jnp.asarray(r.tokens[None]),
                r.max_new_tokens))[0]
            assert np.array_equal(np.asarray(c.tokens), want), f"rid {r.rid}"
        by = {c.rid: c for c in done}
        assert by[0].cached_tokens == 0             # cold, registers P
        assert by[1].cached_tokens == 16            # shares both P blocks
        assert by[2].cached_tokens == 15            # full hit: COW clamps n-1
        eng.pool.blocks.check()

    def test_token_identity_under_eviction_pressure(self, smoke_lm):
        cfg, params = smoke_lm
        # shrink the pool: 8 rows x 32 deep / block 8 = 32 physical blocks,
        # then stream 16 distinct prompts so released cache blocks must be
        # evicted to admit newcomers
        pol = BucketPolicy(num_slots=8, prompt_buckets=(8, 16, 24),
                           seq_max=32)
        reqs = synthetic_requests(16, pattern="burst", min_prompt=17,
                                  max_prompt=24, min_new=2, max_new=5,
                                  vocab=cfg.vocab_size, seed=21)
        eng, stats, _ = _run_pair(cfg, params, reqs, policy=pol,
                                  max_batch=8, max_prompt=24, max_new=8)
        assert eng.pool.blocks.num_blocks == 32
        assert eng.pool.blocks.evictions > 0        # pressure actually hit
        assert stats.num_requests == 16

    def test_prefix_cache_with_paged_kernel(self, smoke_lm):
        cfg, params = smoke_lm
        reqs = synthetic_requests(5, pattern="burst", min_prompt=18,
                                  max_prompt=28, min_new=3, max_new=6,
                                  vocab=cfg.vocab_size, prefix_share=0.8,
                                  shared_prefix_len=16, seed=17)
        eng = Engine(params, cfg, max_batch=4, max_prompt=32, max_new=8,
                     block_size=8)
        assert eng.cfg.attn_impl == "paged"
        done, stats = eng.run(reqs)
        for r, c in zip(reqs, done):
            want = np.asarray(greedy_generate(
                params, cfg, jnp.asarray(r.tokens[None]),
                r.max_new_tokens))[0]
            assert np.array_equal(np.asarray(c.tokens), want), f"rid {r.rid}"
        assert stats.cache_hit_requests >= 1
        eng.pool.blocks.check()
