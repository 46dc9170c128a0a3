"""Continuous-batching engine vs static batching across arrival patterns.

Both scheduling policies run through the SAME engine machinery (jitted
programs, bucket policy, block-table pool) — only the scheduler differs:
continuous refills a row the moment it frees; static waits for the whole
pool to drain (the classic batch-serving baseline, and exactly what
`launch/serve.py` did pre-engine).  The delta therefore isolates the
scheduling policy: fewer pool-wide decode steps (no dead rows riding to the
batch max) and no batch-boundary waiting.

CPU smoke scale; deterministic workloads (`serving.engine.workload`), wall
clock measured after a full compile warmup.  Emits the harness CSV rows and,
with --jsonl, per-run records `benchmarks.report` renders into the serving
latency-percentile section.

    PYTHONPATH=src python -m benchmarks.run --only serve
    PYTHONPATH=src python -m benchmarks.serve_engine --jsonl serve_engine.jsonl
"""
from __future__ import annotations

import argparse
import json

import jax

NUM_REQUESTS = 16
MAX_PROMPT = 48
MAX_NEW = 24


def _model():
    from repro.configs.registry import get_smoke_config
    from repro.models import init_lm

    cfg = get_smoke_config("internlm2-1.8b")
    return cfg, init_lm(jax.random.PRNGKey(0), cfg)


def _engine(cfg, params):
    from repro.serving.engine import Engine

    eng = Engine(params, cfg, max_batch=8, max_prompt=MAX_PROMPT,
                 max_new=MAX_NEW)
    return eng, eng.calibrate_step_s()


def run_patterns(cfg, eng, step_s):
    from repro.serving.engine import PATTERNS, synthetic_requests

    rows, records = [], []
    for pattern in PATTERNS:
        reqs = synthetic_requests(
            NUM_REQUESTS, pattern=pattern, min_prompt=4,
            max_prompt=MAX_PROMPT, min_new=4, max_new=MAX_NEW,
            vocab=cfg.vocab_size, step_s=step_s, seed=17)
        out = {}
        for policy in ("continuous", "static"):
            done, stats = eng.run(reqs, policy=policy)
            out[policy] = stats
            us_per_tok = stats.wall_s / max(stats.total_generated, 1) * 1e6
            rows.append((f"serve_engine/{pattern}/{policy}",
                         f"{us_per_tok:.1f}", f"{stats.tok_s:.1f}_tok_s"))
            records.append({"pattern": pattern, "policy": policy,
                            **stats.to_json()})
        speedup = out["continuous"].tok_s / max(out["static"].tok_s, 1e-9)
        step_ratio = (out["static"].decode_steps
                      / max(out["continuous"].decode_steps, 1))
        rows.append((f"serve_engine/{pattern}/speedup", "0",
                     f"{speedup:.2f}x_tok_s_{step_ratio:.2f}x_steps"))
    return rows, records


def run(jsonl_path=None):
    cfg, params = _model()
    eng, step_s = _engine(cfg, params)
    rows, records = run_patterns(cfg, eng, step_s)
    if jsonl_path:
        with open(jsonl_path, "w") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--jsonl", default=None,
                    help="also write per-run stats records for "
                         "benchmarks.report --serve")
    args = ap.parse_args()
    for name, us, derived in run(args.jsonl):
        print(f"{name},{us},{derived}")


if __name__ == "__main__":
    main()
