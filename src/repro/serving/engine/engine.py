"""Continuous-batching serving engine over a block-table KV pool.

One `Engine` owns: the bucket policy (shapes snapped to the hardware tile
lattice — `buckets`), a `PagedPool` of physical KV blocks with a fixed
lattice of decode rows (`kv_pool`: block tables, a content-addressed prefix
cache, copy-on-write sharing), and a bounded set of jitted programs:

  * one suffix-prefill program per prompt bucket — a single request's
    uncached suffix, right-padded to the bucket, prefilled against the
    row's gathered (1, seq_max) cache view at cache_index = the cached
    prefix length (a cold prompt is a suffix at 0), then scattered back
    into the row's blocks;
  * ONE decode program for the whole row lattice — every step advances all
    rows one token: each row writes its token into its tail block
    (vector cache_index + block tables) and attends over its live pages
    through the Pallas paged-decode kernel; dead rows ride along masked;
  * a sampling program (greedy + temperature with per-request PRNG streams).

The host loop interleaves admission (prefill into freed rows) with pool
decode steps — continuous batching.  `policy="static"` runs the same
machinery but only refills the pool once it has fully drained, which is the
static-batch baseline the benchmarks compare against.

Failure semantics (see `request` module docstring for the finish_reason
catalog): `run()` never raises for a per-request problem.  Invalid requests
become `rejected` completions before they touch a slot; admission control
(`scheduler.ShedPolicy`) sheds under overload; per-request deadlines time
out with partial results; and KV backpressure mid-decode (block-pool
exhaustion during COW/tail growth) preempts the youngest sequence with
exact rollback — its full KV blocks are committed to the prefix cache, the
request re-queues, and on re-admission only the (≤ one block) uncached
tail is re-prefilled, so outputs stay token-identical.
Retries are bounded; a request that exhausts them completes as
`preempted-retry-exhausted` with whatever tokens it has.

Per-request timing (TTFT, inter-token gaps) is recorded on the engine clock
and aggregated by `request.EngineStats`.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from ...configs.base import ModelConfig
from ...core.hardware import Hardware, running_hardware
from ...models import apply_lm
from ...models.layers import compute_dtype
from ...models.linear import cast_linear_params
from .buckets import BucketPolicy, make_policy
from .kv_pool import PagedPool, PoolExhausted
from .request import Completion, EngineStats, Request
from .scheduler import RequestQueue, Scheduler, ShedPolicy


def _check_supported(cfg: ModelConfig) -> None:
    if cfg.family not in ("dense", "moe"):
        raise NotImplementedError(
            f"engine v1 serves attention-based decoders (dense/moe); "
            f"got family={cfg.family!r}")
    if cfg.attn_type not in ("gqa", "mla"):
        raise NotImplementedError(
            f"engine v1 serves GQA and MLA attention; got "
            f"attn_type={cfg.attn_type!r}")
    if cfg.pos_emb != "rotary":
        raise NotImplementedError("engine v1 requires rotary positions")
    if cfg.is_encoder_decoder or cfg.num_patches:
        raise NotImplementedError("engine v1 serves text-only decoders")


# The programs' last argument and output is a device-resident count of the
# drop-free MoE layers' routing (`models.moe.MOE_STATS` per program kind),
# added to on every call and read once when `Engine.run` ends: no step
# waits on it.
MOE_COUNTERS = ("moe.routed_held", "moe.routed_total", "moe.held_rows_max",
                "moe.held_active", "moe.prefill_routed_held",
                "moe.prefill_routed_total", "moe.prefill_held_rows_max",
                "moe.prefill_held_active")
_DECODE, _PREFILL = slice(0, 4), slice(4, 8)


def _prompt_mask(tokens, true_len):
    return jnp.arange(tokens.shape[1])[None, :] < true_len


def _make_prefix_prefill(cfg: ModelConfig):
    """Cache-backed suffix prefill.

    (params, tokens (1, bucket), true_len, start, contig, counts)
        -> (logits, contig, counts)

    `contig` is the row's gathered contiguous (1, seq_max) cache view:
    positions [0, start) hold live prefix-cache KV, and the suffix tokens are
    prefilled at cache_index = start (positions start..start+bucket).  A cold
    prompt is just start = 0 over a garbage view — one program covers both.
    The view is donated (updated in place, then scattered back to blocks).
    """

    def prefill(params, tokens, true_len, start, caches, counts):
        logits, caches, _, st = apply_lm(
            params, tokens, cfg, caches=caches, cache_index=start,
            token_mask=_prompt_mask(tokens, true_len), return_stats=True)
        last = jax.lax.dynamic_slice_in_dim(logits, true_len - 1, 1, axis=1)
        return last[:, 0], caches, counts.at[_PREFILL].add(st)

    return jax.jit(prefill, donate_argnums=(4, 5))


def _make_decode_bt(cfg: ModelConfig):
    """(params, tok (rows, 1), caches, pos (rows,), tables, counts) ->
    (logits, caches, counts).

    The caches are the physical block pool; pos is each row's write
    position (== live kv length, 0 for a dead row) and tables its block
    table.  The pool is donated so every step writes it in place."""

    def decode(params, tok, caches, pos, tables, counts):
        logits, caches, _, st = apply_lm(
            params, tok, cfg, caches=caches, cache_index=pos, decode=True,
            block_tables=tables, token_mask=(pos > 0)[:, None],
            return_stats=True)
        return logits[:, -1], caches, counts.at[_DECODE].add(st)

    return jax.jit(decode, donate_argnums=(2, 5))


def _make_sampler():
    """(logits (n, v), temps, seeds, steps) -> tokens (n,) int32.

    temperature 0 -> argmax; else categorical with key fold_in(seed, step),
    so a request's sample stream is independent of slot placement and step
    timing (reproducible across scheduling policies — and across
    preemption/resume, which re-enters the stream at the same step index).
    """

    def sample(logits, temps, seeds, steps):
        greedy = jnp.argmax(logits, -1).astype(jnp.int32)

        def one(lg, t, sd, st):
            key = jax.random.fold_in(jax.random.PRNGKey(sd), st)
            return jax.random.categorical(
                key, lg / jnp.maximum(t, 1e-6)).astype(jnp.int32)

        sampled = jax.vmap(one)(logits, temps, seeds, steps)
        return jnp.where(temps > 0, sampled, greedy)

    return jax.jit(sample)


@dataclasses.dataclass
class _SlotState:
    req: Request
    generated: List[int]
    last_t_s: float            # engine-clock time of the latest token
    first_token_s: float
    itl_s: List[float]
    cached_tokens: int = 0     # prompt KV served from the prefix cache
    preemptions: int = 0       # times this request has been preempted
    admit_seq: int = 0         # monotonic admission index (youngest = max)


@dataclasses.dataclass
class _ResumeState:
    """Rolled-back progress of a preempted request awaiting re-admission.

    `generated` are the tokens already produced; all KV up to the last full
    block was committed to the prefix cache at preemption, so re-admission
    re-prefills at most one block of tail."""
    generated: List[int]
    first_token_s: float
    last_t_s: float
    itl_s: List[float]
    cached_tokens: int
    attempts: int              # preemptions + failed re-admissions so far


class Engine:
    """Continuous-batching engine; see module docstring.

    The engine serves its own copy of `params`: the GEMM weights in the
    compute dtype (`models.linear.cast_linear_params`), everything else as
    given; `params_cast_bytes` counts the bytes of the leaves it cast.

    `use_paged_kernel` and `prefix_cache` take only True: the engine has one
    block-table pool and one paged decode kernel.  They stay as parameters
    for callers that still pass them, and False raises ValueError."""

    def __init__(self, params, cfg: ModelConfig, *,
                 max_batch: int = 8, max_prompt: int = 64,
                 max_new: int = 64, hw: Optional[Hardware] = None,
                 policy: Optional[BucketPolicy] = None,
                 use_paged_kernel: bool = True,
                 grow_batch: bool = False,
                 prefix_cache: bool = True,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 kv_dtype: str = "auto",
                 preempt_retries: int = 4):
        for name, on in (("use_paged_kernel", use_paged_kernel),
                         ("prefix_cache", prefix_cache)):
            if not on:
                raise ValueError(
                    f"{name}={on!r}: the engine has one block-table KV pool "
                    f"and one paged decode kernel, so {name} takes only True")
        _check_supported(cfg)
        cfg = dataclasses.replace(cfg, attn_impl="paged")
        if cfg.num_experts:
            # served tokens never depend on their batch neighbours: no
            # capacity, no drops (training keeps the capacity path)
            cfg = dataclasses.replace(cfg, moe_dispatch="dropless")
        from ...models.blocks import KV_DTYPES
        if kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"unknown kv_dtype {kv_dtype!r}; valid: {list(KV_DTYPES)}")
        if kv_dtype != "auto":
            # int8 pool: k/v leaves store 1 byte/elem + f32 per-(token, head)
            # scale leaves; everything downstream (pools, prefill/decode
            # programs, paged kernels) keys off cfg.kv_dtype
            cfg = dataclasses.replace(cfg, kv_dtype=kv_dtype)
        # the serving copy: GEMM weights in the compute dtype, cast once here
        # rather than in every program call (the caller keeps its own tree)
        self.params = cast_linear_params(params, compute_dtype(cfg.dtype),
                                         tied=cfg.tie_embeddings)
        self.params_cast_bytes = sum(
            new.nbytes for new, old in zip(jax.tree.leaves(self.params),
                                           jax.tree.leaves(params))
            if new is not old)
        self.cfg = cfg
        hw = hw or running_hardware()
        self.hw = hw
        self.drift: Optional[obs.DriftMonitor] = None   # attached by callers
        self.policy = policy or make_policy(
            cfg, hw, max_batch=max_batch, max_prompt=max_prompt,
            max_seq=max_prompt + max_new, grow_batch=grow_batch)
        self.preempt_retries = preempt_retries
        bs = block_size or self._pick_block_size(hw)
        self.pool = PagedPool(cfg, self.policy.num_slots, self.policy.seq_max,
                              compute_dtype(cfg.dtype), block_size=bs,
                              num_blocks=num_blocks)
        # every admission is a cache-backed *suffix* prefill (a cold prompt
        # is a suffix at start=0); bucketed on the suffix length
        pf = self._counting(_make_prefix_prefill(cfg))
        self._prefills = {b: pf for b in self.policy.prompt_buckets}
        self._decode = self._counting(_make_decode_bt(cfg))
        self._sample = _make_sampler()
        # per-slot device-facing state (dead slots: token 0, temp 0)
        n = self.policy.num_slots
        self._last_tok = np.zeros(n, np.int32)
        self._temps = np.zeros(n, np.float32)
        self._seeds = np.zeros(n, np.int32)
        self._steps = np.zeros(n, np.int32)
        self.decode_steps = 0
        self.prefills = 0
        self.preemptions = 0
        self.resumes = 0
        self.step_s_estimate = 0.0      # set by calibrate_step_s
        self._resume: Dict[int, _ResumeState] = {}
        self._admit_attempts: Dict[int, int] = {}
        self._admit_counter = 0
        self._queue: Optional[RequestQueue] = None
        self._faults = None
        self._counts = jnp.zeros(len(MOE_COUNTERS), jnp.int32)
        self.moe_counts: Dict[str, int] = {}    # the last run's, when MoE

    def _counting(self, program):
        """The program as the engine calls it, `(..) -> (logits, caches)`:
        the routing counts it adds stay on the device (`MOE_COUNTERS`)."""
        def call(*args):
            logits, caches, self._counts = program(*args, self._counts)
            return logits, caches
        return call

    def _publish_counts(self) -> None:
        """Read the run's MoE routing counts (one readback, after the last
        step's tokens were read) and add them to the obs counters."""
        if self.cfg.moe_dispatch != "dropless":
            return
        self.moe_counts = dict(zip(MOE_COUNTERS,
                                   np.asarray(self._counts).tolist()))
        if obs.enabled():
            for name, n in self.moe_counts.items():
                obs.counter(name).inc(n)

    def _pick_block_size(self, hw: Hardware) -> int:
        """Physical KV block size: a tile-lattice choice, taken from the
        `paged_decode_blocktable_pool` tuning-cache entry for this pool
        geometry when one exists (see
        `tuning.search.autotune_paged_decode_blocktable`), else the smallest
        lattice divisor of seq_max >= 16 — fine-grained enough to share
        prefixes, still a whole number of register tiles."""
        from ...tuning.cache import lookup
        from ...tuning.candidates import bucket_steps, sublane_granule
        cfg = self.cfg
        n, s_max = self.policy.num_slots, self.policy.seq_max
        dt = jnp.dtype(compute_dtype(cfg.dtype))
        entry = lookup(
            "paged_decode_blocktable_pool",
            (n, n, s_max, cfg.num_kv_heads, cfg.num_heads, cfg.head_dim),
            dt.name, hw.name)
        if entry is not None and s_max % entry.blocks["block_size"] == 0:
            return int(entry.blocks["block_size"])
        sub = sublane_granule(hw, dt.itemsize)
        divisors = [b for b in bucket_steps(s_max, sub) if s_max % b == 0]
        for b in divisors:
            if b >= 16:
                return b
        return divisors[-1] if divisors else s_max

    def reset_stats(self) -> None:
        """Zero the step counters.  run() does this itself on entry, so the
        counters (and EngineStats) are always per-run; kept public for
        callers that read the counters between partial workloads."""
        self.decode_steps = 0
        self.prefills = 0
        self.preemptions = 0
        self.resumes = 0

    def calibrate_step_s(self) -> float:
        """Warm every bucket's prefill + the pool decode program, then time
        one decode step (used to express arrival patterns in machine-relative
        units, and as the TTFT predictor of `ShedPolicy`).  First run pays
        the compiles; the second is the timer."""
        from .request import Request as _Req
        # gen budget clamped so bucket-wide warm prompts still fit the pool;
        # distinct token fill per bucket so the prefix cache can't dedupe the
        # warm prompts — every bucket must compile its full-width (cold)
        # suffix prefill, not ride an earlier bucket's cached prefix
        warm = [_Req(rid=i, tokens=np.full(b, 1 + i, np.int32),
                     max_new_tokens=min(4, max(self.policy.seq_max - b, 1)))
                for i, b in enumerate(self.policy.prompt_buckets)]
        self.run(warm)
        _, stats = self.run(warm)
        self.step_s_estimate = stats.wall_s / max(stats.decode_steps, 1)
        return self.step_s_estimate

    # -- admission -----------------------------------------------------------

    def _admission_error(self, req: Request) -> Optional[str]:
        """Why `req` can never be served (None when it can).  Checked before
        a request enters the queue, so a bad request never touches a slot —
        and never takes down the batch it arrived with."""
        if req.prompt_len < 1:
            return "empty prompt"
        if req.max_new_tokens < 1:
            return f"max_new_tokens {req.max_new_tokens} < 1"
        toks = np.asarray(req.tokens)
        if not np.issubdtype(toks.dtype, np.integer):
            return f"prompt tokens must be integers, got {toks.dtype}"
        lo, hi = int(toks.min()), int(toks.max())
        if lo < 0 or hi >= self.cfg.padded_vocab_size:
            return (f"prompt token ids [{lo}, {hi}] outside "
                    f"[0, {self.cfg.padded_vocab_size})")
        try:
            self.policy.prompt_bucket(req.prompt_len)
        except ValueError as e:
            return str(e)
        if req.prompt_len + req.max_new_tokens > self.policy.seq_max:
            return (f"prompt {req.prompt_len} + gen {req.max_new_tokens} "
                    f"exceeds pool depth {self.policy.seq_max}")
        need = -(-req.prompt_len // self.pool.block_size)
        if need > self.pool.blocks.num_blocks:
            return (f"prompt needs {need} KV blocks; the pool only has "
                    f"{self.pool.blocks.num_blocks}")
        return None

    def _reject(self, req: Request, detail: str,
                done: List[Completion]) -> None:
        done.append(Completion(
            rid=req.rid, prompt_len=req.prompt_len, tokens=[],
            arrival_s=req.arrival_s, first_token_s=None, done_s=self._now(),
            finish_reason="rejected", detail=detail))
        if obs.enabled():
            obs.counter("engine.rejected").inc()
            obs.instant("reject", rid=req.rid, detail=detail)

    def _drop(self, req: Request, reason: str, detail: str,
              done: List[Completion]) -> None:
        """Finalize a request dropped before (re-)admission: shed / timeout
        from the scheduler, or a dead-end re-admission.  A preempted request
        keeps its partial tokens; its reason stays `timeout` when the
        deadline fired, else becomes `preempted-retry-exhausted` (it *was*
        being served — "shed" would misreport it as never admitted)."""
        res = self._resume.pop(req.rid, None)
        if res is None:
            done.append(Completion(
                rid=req.rid, prompt_len=req.prompt_len, tokens=[],
                arrival_s=req.arrival_s, first_token_s=None,
                done_s=self._now(), finish_reason=reason, detail=detail))
        else:
            reason = reason if reason == "timeout" else \
                "preempted-retry-exhausted"
            done.append(Completion(
                rid=req.rid, prompt_len=req.prompt_len,
                tokens=res.generated, arrival_s=req.arrival_s,
                first_token_s=res.first_token_s, done_s=self._now(),
                itl_s=res.itl_s, cached_tokens=res.cached_tokens,
                finish_reason=reason, detail=detail,
                preemptions=res.attempts))
        if obs.enabled():
            obs.counter(f"engine.{reason.split('-')[0]}").inc()
            obs.instant("drop", rid=req.rid, reason=reason, detail=detail)

    def _admit(self, req: Request, slot: int,
               states: Dict[int, _SlotState],
               done: List[Completion]) -> None:
        res = self._resume.pop(req.rid, None)
        bucket = self.policy.prompt_bucket(req.prompt_len)
        with obs.span("admit", rid=req.rid, slot=slot,
                      prompt_len=req.prompt_len, bucket=bucket,
                      resume=res is not None):
            try:
                logits, cached = self._prefill(req, slot, res)
            except PoolExhausted as e:
                # admission raced a COW burst / held blocks: the slot is
                # returned, the request re-queued with a bounded retry budget
                self.pool.release(slot)
                self._retry_admission(req, res, f"pool exhausted: {e}", done)
                return
            except ValueError as e:
                # a resumed request whose warm blocks were evicted can
                # outgrow the prompt-bucket lattice — a dead end, not a bug
                self.pool.release(slot)
                self._drop_or_requeue_dead_end(req, res, str(e), done)
                return
            sp = req.sampling
            m = len(res.generated) if res is not None else 0
            with obs.span("sample", cat="sample", batch=1):
                tok = self._sample(
                    logits, jnp.asarray([sp.temperature], jnp.float32),
                    jnp.asarray([sp.seed or req.rid], jnp.int32),
                    jnp.asarray([m], jnp.int32))
                tok0 = int(np.asarray(tok)[0])
        self.prefills += 1
        self._admit_counter += 1
        self._admit_attempts.pop(req.rid, None)
        if obs.enabled():
            obs.counter("engine.prefills").inc()
            obs.counter("engine.tokens_generated").inc()
            obs.counter("engine.prompt_tokens_cached").inc(cached)
        t = self._now()
        self._last_tok[slot] = tok0
        self._temps[slot] = sp.temperature
        self._seeds[slot] = sp.seed or req.rid
        self._steps[slot] = m + 1
        if res is None:
            st = _SlotState(req=req, generated=[tok0], last_t_s=t,
                            first_token_s=t, itl_s=[], cached_tokens=cached,
                            admit_seq=self._admit_counter)
        else:
            # resume: sampling re-entered the request's PRNG stream at step
            # m, so the continuation is what the uninterrupted run would
            # have produced; the preemption stall lands in the ITL trace
            self.resumes += 1
            if obs.enabled():
                obs.counter("engine.resumes").inc()
            st = _SlotState(req=req, generated=res.generated + [tok0],
                            last_t_s=t, first_token_s=res.first_token_s,
                            itl_s=res.itl_s + [t - res.last_t_s],
                            cached_tokens=res.cached_tokens,
                            preemptions=res.attempts,
                            admit_seq=self._admit_counter)
        if self._finished(st):
            self._complete(slot, st, states, done)
        elif (st.req.deadline_s is not None
              and t > st.req.arrival_s + st.req.deadline_s):
            self._complete(slot, st, states, done, reason="timeout",
                           detail=f"deadline {st.req.deadline_s:.3f}s "
                                  f"expired after first token")
        else:
            states[slot] = st

    def _retry_admission(self, req: Request, res: Optional[_ResumeState],
                         detail: str, done: List[Completion]) -> None:
        attempts = (res.attempts if res is not None
                    else self._admit_attempts.get(req.rid, 0)) + 1
        if attempts > self.preempt_retries:
            if res is not None:
                self._resume[req.rid] = res   # _drop consumes it
                self._drop(req, "preempted-retry-exhausted",
                           f"{detail} ({attempts} attempts)", done)
            else:
                self._drop(req, "shed",
                           f"{detail} ({attempts} admission attempts)", done)
            return
        if res is not None:
            res.attempts = attempts
            self._resume[req.rid] = res
        else:
            self._admit_attempts[req.rid] = attempts
        self._queue.push(req)
        if obs.enabled():
            obs.counter("engine.admission_retries").inc()

    def _drop_or_requeue_dead_end(self, req: Request,
                                  res: Optional[_ResumeState], detail: str,
                                  done: List[Completion]) -> None:
        if res is not None:
            self._resume[req.rid] = res
            self._drop(req, "preempted-retry-exhausted", detail, done)
        else:
            self._reject(req, detail, done)

    def _prefill(self, req: Request, slot: int,
                 res: Optional[_ResumeState]) -> Tuple[jax.Array, int]:
        """Admission: bind a block table (sharing every cached full
        prefix block), prefill only the uncached suffix, scatter the new
        blocks back, and register the prompt's full blocks for future hits.
        A resumed request prefills prompt + generated-so-far; its full
        blocks were committed at preemption, so the suffix is at most one
        block plus the un-advanced last token.
        Returns (last-token logits (1, v), cached token count)."""
        pool: PagedPool = self.pool
        if res is None:
            tokens = np.asarray(req.tokens, np.int32)
        else:
            tokens = np.concatenate(
                [np.asarray(req.tokens, np.int32),
                 np.asarray(res.generated, np.int32)])
        seq = pool.alloc_sequence(slot, tokens)
        p = seq.num_cached
        suffix = np.asarray(tokens[p:], np.int32)
        # a resume whose warm blocks were evicted may present a suffix wider
        # than the prompt lattice: prompt_bucket raises and _admit converts
        bucket = self.policy.prompt_bucket(len(suffix))
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(suffix)] = suffix
        contig = pool.gather(slot)
        with obs.span("prefill", bucket=bucket, rid=req.rid,
                      cached_tokens=p):
            logits, contig = self._prefills[bucket](
                self.params, jnp.asarray(padded),
                jnp.asarray(len(suffix), jnp.int32),
                jnp.asarray(p, jnp.int32), contig)
        pool.scatter(slot, contig, p // pool.block_size)
        pool.commit(slot, tokens)
        if obs.enabled():
            obs.counter("engine.prefill_tokens").inc(len(suffix))
            obs.counter("engine.prefill_bucket_tokens").inc(bucket)
            obs.counter("kv.prefix_hit_tokens").inc(p)
            self._kv_gauges()
        cached = p if res is None else res.cached_tokens
        return logits, cached

    def _finished(self, st: _SlotState) -> bool:
        if len(st.generated) >= st.req.max_new_tokens:
            return True
        eos = st.req.eos_id
        return eos is not None and st.generated[-1] == eos

    def _complete(self, slot: int, st: _SlotState,
                  states: Dict[int, _SlotState],
                  done: List[Completion], *, reason: Optional[str] = None,
                  detail: str = "") -> None:
        if reason is None:
            eos = st.req.eos_id
            reason = ("stop" if eos is not None and st.generated
                      and st.generated[-1] == eos else "length")
        done.append(Completion(
            rid=st.req.rid, prompt_len=st.req.prompt_len,
            tokens=st.generated, arrival_s=st.req.arrival_s,
            first_token_s=st.first_token_s, done_s=self._now(),
            itl_s=st.itl_s, cached_tokens=st.cached_tokens,
            finish_reason=reason, detail=detail,
            preemptions=st.preemptions))
        states.pop(slot, None)
        self._temps[slot] = 0.0
        self.pool.release(slot)
        if obs.enabled():
            obs.counter("engine.requests_completed").inc()
            if reason == "timeout":
                obs.counter("engine.timeout").inc()
            obs.instant("complete", rid=st.req.rid, slot=slot,
                        tokens=len(st.generated), reason=reason)

    # -- preemption ----------------------------------------------------------

    def _pick_victim(self, states: Dict[int, _SlotState]) -> int:
        """Youngest live sequence (most recent admission): it has the least
        progress to roll back and the fewest tokens to re-prefill."""
        return max(states, key=lambda s: states[s].admit_seq)

    def _preempt(self, slot: int, states: Dict[int, _SlotState],
                 done: List[Completion]) -> None:
        """Exact rollback of `slot` under KV backpressure: commit every full
        block of its written KV to the prefix cache (so re-admission only
        re-prefills the tail), release the row, and re-queue the request at
        its original arrival position.  Out of retry budget -> complete as
        preempted-retry-exhausted with the tokens generated so far."""
        st = states.pop(slot)
        self.preemptions += 1
        self._temps[slot] = 0.0
        attempts = st.preemptions + 1
        if obs.enabled():
            obs.counter("engine.preemptions").inc()
            obs.instant("preempt", rid=st.req.rid, slot=slot,
                        generated=len(st.generated), attempts=attempts)
        if attempts > self.preempt_retries:
            self.pool.release(slot)
            done.append(Completion(
                rid=st.req.rid, prompt_len=st.req.prompt_len,
                tokens=st.generated, arrival_s=st.req.arrival_s,
                first_token_s=st.first_token_s, done_s=self._now(),
                itl_s=st.itl_s, cached_tokens=st.cached_tokens,
                finish_reason="preempted-retry-exhausted",
                detail=f"preempted {attempts}x; retry budget "
                       f"{self.preempt_retries}",
                preemptions=attempts))
            return
        # KV in the pool covers prompt + generated[:-1] (the newest token
        # has not been fed to decode yet); registering those full blocks is
        # what makes the rollback exact-and-cheap instead of a full refill
        written = np.concatenate(
            [np.asarray(st.req.tokens, np.int32),
             np.asarray(st.generated[:-1], np.int32)])
        self.pool.commit(slot, written)
        self.pool.release(slot)
        self._resume[st.req.rid] = _ResumeState(
            generated=st.generated, first_token_s=st.first_token_s,
            last_t_s=st.last_t_s, itl_s=st.itl_s,
            cached_tokens=st.cached_tokens, attempts=attempts)
        self._queue.push(st.req)

    # -- main loop -----------------------------------------------------------

    def _now(self) -> float:
        return time.perf_counter() - self._t0

    def _kv_gauges(self) -> None:
        """Publish pool occupancy: rows, then blocks."""
        obs.gauge("engine.live_slots").set(self.pool.num_active)
        obs.gauge("engine.free_slots").set(self.pool.num_free)
        bp = self.pool.blocks
        obs.gauge("kv.free_blocks").set(bp.num_free_blocks)
        obs.gauge("kv.cached_blocks").set(bp.num_cached_blocks)
        obs.gauge("kv.referenced_blocks").set(bp.num_referenced_blocks)

    def run(self, requests: List[Request], *,
            policy: str = "continuous",
            shed: Optional[ShedPolicy] = None,
            faults=None,
            check_invariants: bool = False) -> Tuple[List[Completion],
                                                     EngineStats]:
        """Serve `requests`; returns (completions sorted by request id,
        aggregate stats).  Every request gets exactly one Completion — no
        per-request condition raises out of this loop (see module
        docstring).  policy="static" = drain-then-refill baseline;
        `shed` = admission control (scheduler.ShedPolicy); `faults` = a
        faults.FaultPlan injecting deterministic failures at step
        boundaries; check_invariants asserts the block-pool invariants
        after every decode step (chaos/CI mode)."""
        self.reset_stats()  # counters (and stats) are per-run
        self._resume = {}
        self._admit_attempts = {}
        self._admit_counter = 0
        self._faults = faults
        if faults is not None:
            faults.reset()
        self._counts = jnp.zeros(len(MOE_COUNTERS), jnp.int32)
        self._t0 = time.perf_counter()
        done: List[Completion] = []
        valid: List[Request] = []
        for req in requests:
            err = self._admission_error(req)
            if err is None:
                valid.append(req)
            else:
                self._reject(req, err, done)
        queue = RequestQueue(valid)
        self._queue = queue
        sched = Scheduler(queue, self.pool, policy, shed=shed)
        states: Dict[int, _SlotState] = {}

        # every pass of the loop runs inside top-level spans: `schedule`,
        # one `admit` per admission, then `idle_wait` or `decode_step` and
        # `bookkeep` (in `_step`)
        while not sched.drained:
            with obs.span("schedule"):
                admits, sheds = sched.admissions(self._now())
                for s in sheds:
                    self._drop(s.req, s.reason, s.detail, done)
                if obs.enabled():
                    obs.gauge("engine.queue_depth").set(len(queue))
                    self._kv_gauges()
            for req, slot in admits:
                self._admit(req, slot, states, done)
            if not states:
                if admits or sheds:
                    continue    # progress was made; re-evaluate immediately
                with obs.span("idle_wait"):
                    nxt = queue.next_arrival_s()
                    now = self._now()
                    if nxt is not None and nxt > now:
                        time.sleep(nxt - now + 1e-4)
                    elif len(queue):
                        # ready requests, an idle pool, and still no
                        # admission: nothing left that could free capacity.
                        # Give injected holds a chance to drain, else fail
                        # the head request rather than spin forever.
                        if faults is not None and faults.drain_holds(self):
                            continue
                        req = queue.pop_ready(now)
                        if req is not None:
                            self._drop_or_requeue_dead_end(
                                req, self._resume.pop(req.rid, None),
                                "unadmittable with an idle pool "
                                "(exceeds usable capacity)", done)
                continue
            self._step(states, done)
            if check_invariants:
                self.pool.blocks.check()

        if faults is not None:
            faults.drain_holds(self)
        if check_invariants:
            self.pool.blocks.check()
        self._faults = None
        self._queue = None
        wall = self._now()
        self._publish_counts()
        if obs.enabled():
            obs.counter("engine.params_cast_bytes").inc(self.params_cast_bytes)
        done.sort(key=lambda c: c.rid)
        return done, EngineStats.collect(done, wall,
                                         decode_steps=self.decode_steps,
                                         prefills=self.prefills,
                                         preemptions=self.preemptions,
                                         resumes=self.resumes)

    def _step(self, states: Dict[int, _SlotState],
              done: List[Completion]) -> None:
        """One pool-wide decode step: every live row advances one token.
        KV backpressure (block exhaustion while making write positions
        appendable) preempts youngest-first instead of raising; preempted
        rows ride through the step masked-dead."""
        if self._faults is not None:
            self._faults.on_step(self, self.decode_steps)
        step = self.decode_steps
        with obs.span("decode_step", step=step, live=len(states),
                      batch=self.policy.num_slots) as dsp:
            # make each live row's write position physically writable
            # (tail-block alloc / copy-on-write) before the device step
            with obs.span("prepare_append", cat="kv", live=len(states)):
                for slot in list(states):
                    if slot not in states:
                        continue    # already preempted as a victim
                    while slot in states:
                        try:
                            self.pool.prepare_append(slot)
                            break
                        except PoolExhausted:
                            self._preempt(self._pick_victim(states),
                                          states, done)
            if not states:
                return      # every row was preempted: nothing to decode
            with obs.span("decode_inputs", step=step):
                logits, self.pool.caches = self._decode(
                    self.params, jnp.asarray(self._last_tok[:, None]),
                    self.pool.caches,
                    jnp.asarray(np.asarray(self.pool.lengths, np.int32)),
                    jnp.asarray(self.pool.tables()))
            with obs.span("sample", cat="sample",
                          batch=self.policy.num_slots):
                toks = np.asarray(self._sample(
                    logits, jnp.asarray(self._temps),
                    jnp.asarray(self._seeds), jnp.asarray(self._steps)))
        with obs.span("bookkeep", step=step, live=len(states)):
            t = self._now()
            self.decode_steps += 1
            if obs.enabled():
                if self.drift is not None:
                    self.drift.observe("decode_step", dsp.dur_s)
                obs.counter("engine.decode_steps").inc()
                obs.counter("engine.decode_rows_live").inc(len(states))
                # pages the paged-decode kernel reads: each live row's KV
                # (MLA: latent), the token this step wrote included
                bs, lens = self.pool.block_size, self.pool.lengths
                obs.counter("engine.decode_latent_pages"
                            if self.cfg.attn_type == "mla"
                            else "engine.decode_kv_pages").inc(
                    sum(-(-(lens[s] + 1) // bs) for s in states))
                obs.counter("engine.tokens_generated").inc(len(states))
                obs.histogram("engine.decode_step_s").observe(dsp.dur_s)
            for slot in list(states):
                st = states[slot]
                tok = int(toks[slot])
                self.pool.advance(slot)
                self._last_tok[slot] = tok
                self._steps[slot] += 1
                st.generated.append(tok)
                st.itl_s.append(t - st.last_t_s)
                st.last_t_s = t
                if self._finished(st):
                    self._complete(slot, st, states, done)
                elif (st.req.deadline_s is not None
                      and t > st.req.arrival_s + st.req.deadline_s):
                    self._complete(
                        slot, st, states, done, reason="timeout",
                        detail=f"deadline {st.req.deadline_s:.3f}s expired "
                               f"after {len(st.generated)} tokens")
