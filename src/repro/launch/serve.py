"""Serving drivers: static batch (baseline) and the continuous-batching
engine (`repro.serving.engine`).

Static batch (the PR-1 behavior, kept as the baseline):

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke \
        --batch 4 --prompt-len 32 --gen 16

Continuous-batching engine — admits a synthetic request stream into the
block-table KV pool (prefix cache, Pallas paged decode), reporting aggregate
tok/s plus per-request TTFT and inter-token latency percentiles:

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
        --engine --smoke --requests 12 --arrival uniform --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..configs.registry import get_config, get_smoke_config
from ..data.pipeline import synthetic_tokens
from ..models import init_lm
from ..serving.serve_step import make_decode_step, make_prefill_step
from .compile_cache import enable_compile_cache


def run_static(cfg, params, args) -> None:
    """Legacy static-batch greedy loop: one jit per (batch, s_max), slots
    idle once a sequence finishes — the baseline the engine improves on."""
    s_max = args.prompt_len + args.gen
    prompts = jnp.asarray(synthetic_tokens(args.seed, 0, args.batch,
                                           args.prompt_len, cfg.vocab_size))
    batch = {"tokens": prompts}
    if cfg.is_encoder_decoder:
        batch["encoder_frames"] = jnp.zeros(
            (args.batch, cfg.encoder_seq, cfg.d_model), jnp.float32)
    prefill = jax.jit(make_prefill_step(cfg, s_max))
    decode = jax.jit(make_decode_step(cfg), donate_argnums=(2,))

    t0 = time.time()
    logits, caches = prefill(params, batch)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    jax.block_until_ready(tok)
    t_prefill = time.time() - t0

    out = [tok]
    idx = jnp.asarray(args.prompt_len, jnp.int32)
    t0 = time.time()
    for _ in range(args.gen - 1):
        logits, caches = decode(params, tok, caches, idx)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out.append(tok)
        idx = idx + 1
    toks = jnp.concatenate(out, axis=1)
    jax.block_until_ready(toks)
    t_decode = time.time() - t0

    print(f"prefill: {args.batch}x{args.prompt_len} in {t_prefill*1e3:.1f} ms")
    print(f"decode:  {args.gen - 1} steps x batch {args.batch} in "
          f"{t_decode*1e3:.1f} ms "
          f"({(args.gen-1)*args.batch/max(t_decode,1e-9):,.0f} tok/s)")
    print("sample:", np.asarray(toks[0, :16]))


def parse_shed_policy(spec: str, step_s: float):
    """`--shed-policy depth=16,slo=0.25,lookahead=4` -> ShedPolicy.
    `step_s` is the calibrated decode-step time (the TTFT predictor)."""
    from ..serving.engine import ShedPolicy

    kw = {}
    for part in filter(None, (p.strip() for p in spec.split(","))):
        key, _, val = part.partition("=")
        if key == "depth":
            kw["max_queue_depth"] = int(val)
        elif key == "slo":
            kw["ttft_slo_s"] = float(val)
        elif key == "lookahead":
            kw["lookahead"] = int(val)
        else:
            raise SystemExit(f"--shed-policy: unknown key {key!r} "
                             f"(valid: depth, slo, lookahead)")
    return ShedPolicy(step_s=step_s, **kw)


def run_engine(cfg, params, args) -> None:
    """Continuous-batching engine over a synthetic request stream."""
    import dataclasses

    from ..serving.engine import Engine, FaultPlan, synthetic_requests

    if args.obs_dump:
        obs.enable()
    watch = None
    if args.watchdog:
        watch = obs.CompileWatch().install()

    eng = Engine(params, cfg, max_batch=args.batch,
                 max_prompt=args.prompt_len, max_new=args.gen,
                 grow_batch=args.grow_batch, kv_dtype=args.kv_dtype)
    pol = eng.policy
    print(f"bucket policy: {pol.num_slots} slots x {pol.seq_max} kv depth, "
          f"prompt buckets {list(pol.prompt_buckets)} "
          f"(<= {pol.num_programs} lowered programs)")

    # compile warmup + one decode-step timing, so arrival patterns are
    # expressed in machine-relative units
    step_s = eng.calibrate_step_s()
    if args.obs_dump:
        eng.drift = obs.DriftMonitor.for_engine(eng.cfg, eng.policy, eng.hw)
    if watch is not None:
        # every program is now compiled; steady-state serving must not re-jit
        print(f"watchdog: {len(watch.records)} compiles during warmup; "
              f"arming — any further compile fails the run")
        watch.arm()

    reqs = synthetic_requests(
        args.requests, pattern=args.arrival, min_prompt=4,
        max_prompt=args.prompt_len, min_new=max(args.gen // 4, 1),
        max_new=args.gen, vocab=cfg.vocab_size, step_s=step_s,
        temperature=args.temperature, seed=args.seed)
    if args.deadline_s is not None:
        reqs = [dataclasses.replace(r, deadline_s=args.deadline_s)
                for r in reqs]
    shed = (parse_shed_policy(args.shed_policy, step_s)
            if args.shed_policy else None)
    faults = None
    if args.chaos_seed is not None:
        faults = FaultPlan.generate(args.chaos_seed, [r.rid for r in reqs],
                                    num_steps=max(args.gen * 2, 8))
        reqs = faults.apply_to_requests(reqs, eng.policy.seq_max)
        print(f"chaos: seed {args.chaos_seed}, request faults "
              f"{faults.request_faults}, {len(faults.events)} step events")
    done, stats = eng.run(reqs, shed=shed, faults=faults,
                          check_invariants=faults is not None)

    if watch is not None:
        watch.check()
        watch.disarm()
        print("watchdog: zero unexpected compiles in steady state")
    print(f"served {stats.num_requests} requests "
          f"({stats.total_generated} tokens) in {stats.wall_s*1e3:.0f} ms "
          f"| {stats.prefills} prefills, {stats.decode_steps} decode steps")
    print(f"aggregate: {stats.tok_s:,.1f} tok/s")
    print(f"TTFT:       p50 {stats.ttft_p50_s*1e3:8.1f} ms   "
          f"p99 {stats.ttft_p99_s*1e3:8.1f} ms")
    print(f"inter-token p50 {stats.itl_p50_s*1e3:8.1f} ms   "
          f"p99 {stats.itl_p99_s*1e3:8.1f} ms")
    if stats.num_ok != stats.num_requests:
        parts = "  ".join(f"{k}={v}" for k, v in stats.finish_reasons.items())
        print(f"outcomes:   {parts}  | goodput {stats.goodput:.3f} "
              f"(preemptions {stats.preemptions}, resumes {stats.resumes})")
    first_ok = next((c for c in done if c.ok), None)
    if first_ok is not None:
        print("sample:", first_ok.tokens[:16])

    if args.obs_dump:
        paths = obs.export_all(args.obs_dump, drift=eng.drift, watch=watch)
        print(f"obs dump: {sorted(paths.values())}")
        print(f"summarize with: python -m repro.obs.view {args.obs_dump}")
    if watch is not None:
        watch.uninstall()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching engine instead of the static "
                         "batch loop")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    # engine-only knobs
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--arrival", default="uniform",
                    choices=("burst", "uniform", "bursty", "longtail"))
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--kv-dtype", default="auto", choices=["auto", "int8"],
                    help="KV-cache storage dtype: int8 halves pool bytes "
                         "(vs bf16) with per-(token, head) f32 scales")
    ap.add_argument("--grow-batch", action="store_true",
                    help="let the advisor grow the slot bucket when the "
                         "calibrated model predicts enough amortization")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request completion deadline in seconds; "
                         "expiry returns the partial result as "
                         "finish_reason=timeout")
    ap.add_argument("--shed-policy", default=None, metavar="SPEC",
                    help="admission control, e.g. 'depth=16,slo=0.25"
                         "[,lookahead=4]': shed beyond a ready-queue depth "
                         "and/or a predicted-TTFT SLO")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="inject a seeded FaultPlan (bad prompts, deadline "
                         "pressure, block steals, COW storms) and assert "
                         "pool invariants every step")
    ap.add_argument("--obs-dump", default=None, metavar="DIR",
                    help="enable observability and write trace/metrics/drift "
                         "dumps to DIR (see `python -m repro.obs.view DIR`)")
    ap.add_argument("--watchdog", action="store_true",
                    help="record every XLA compile, arm after calibration, "
                         "and FAIL on any steady-state recompile")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = init_lm(jax.random.PRNGKey(args.seed), cfg)
    if args.engine:
        run_engine(cfg, params, args)
    else:
        run_static(cfg, params, args)


if __name__ == "__main__":
    main()
