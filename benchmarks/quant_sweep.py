"""Low-precision sweep: bf16 vs int8 vs fp8-emulated GEMM/MLP, f32 vs int8
KV decode, and the dtype-aware analytic pricing that justifies the paths.

Two kinds of signal, matching the container reality (CPU-only; Pallas runs
in interpret mode):

  * CPU smoke wall-clock + parity — the int8/fp8 kernels run end-to-end and
    land within quantization noise of the f32 GEMM.  Absolute interpret-mode
    times are NOT TPU times; they only prove the paths execute.
  * Analytic pricing (`core.gemm_model.precision_candidates` on tpu_v5e) —
    where int8 actually wins: a memory-bound decode GEMM moves ~half the
    weight bytes, so the roofline prices it near 1.9x over bf16; compute-
    bound train GEMMs stay bf16 (the model prices bandwidth only — the int8
    MXU rate bonus would only widen the win).  This is the number a TPU
    deployment of the quantized path is expected to track.

Plus the serving-economics row: `repro.quant.kv_bytes_per_token` prices KV
slots-per-GiB at kv_dtype="auto" vs "int8" for real registry shapes.

Emits harness CSV rows; --jsonl writes records for `benchmarks.report
--quant`; --json persists the BENCH_quant.json summary the docs quote.

    PYTHONPATH=src python -m benchmarks.run --only quant
    PYTHONPATH=src python -m benchmarks.quant_sweep --jsonl quant.jsonl \
        --json BENCH_quant.json
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from .common import wall_us

GEMM_M, GEMM_K, GEMM_N = 256, 256, 256  # tile-aligned CPU-smoke GEMM
MLP_M, MLP_H, MLP_F = 128, 256, 512
HW = "tpu_v5e"
ARCHS = ("internlm2-1.8b", "qwen1.5-4b")


def _gemm_smoke(records):
    from repro.kernels.matmul.ops import matmul
    from repro.kernels.quantized.ops import fp8_matmul, int8_matmul

    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (GEMM_M, GEMM_K)) * 0.5
    w = jax.random.normal(jax.random.fold_in(key, 1), (GEMM_K, GEMM_N)) * 0.5
    want = np.asarray(a @ w)
    denom = np.abs(want).max()

    impls = {
        "f32_pallas": lambda a: matmul(a, w, interpret=True),
        "int8": lambda a: int8_matmul(a, w, interpret=True),
        "fp8_e4m3": lambda a: fp8_matmul(a, w, interpret=True),
    }
    rows = []
    for name, fn in impls.items():
        us = wall_us(fn, a, iters=2, warmup=1, jit=False)
        err = float(np.abs(np.asarray(fn(a)) - want).max() / denom)
        rows.append((f"quant_sweep/gemm_{name}", round(us, 1),
                     f"rel_err={err:.4f};shape={GEMM_M}x{GEMM_K}x{GEMM_N}"))
        records.append({"type": "gemm_cpu", "impl": name, "m": GEMM_M,
                        "k": GEMM_K, "n": GEMM_N, "cpu_us": us,
                        "rel_err": err})
    return rows


def _mlp_smoke(records):
    from repro.kernels.fused_mlp.ops import fused_mlp_hidden
    from repro.kernels.quantized.ops import int8_fused_mlp_hidden

    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (MLP_M, MLP_H)) * 0.5
    wg = jax.random.normal(jax.random.fold_in(key, 1), (MLP_H, MLP_F)) * 0.3
    wu = jax.random.normal(jax.random.fold_in(key, 2), (MLP_H, MLP_F)) * 0.3
    want = np.asarray(fused_mlp_hidden(x, wg, wu, mlp_type="swiglu",
                                       interpret=True))
    denom = np.abs(want).max()
    impls = {
        "fused_f32": lambda x: fused_mlp_hidden(x, wg, wu, mlp_type="swiglu",
                                                interpret=True),
        "fused_int8": lambda x: int8_fused_mlp_hidden(x, wg, wu,
                                                      interpret=True),
    }
    rows = []
    for name, fn in impls.items():
        us = wall_us(fn, x, iters=2, warmup=1, jit=False)
        err = float(np.abs(np.asarray(fn(x)) - want).max() / denom)
        rows.append((f"quant_sweep/mlp_{name}", round(us, 1),
                     f"rel_err={err:.4f};shape={MLP_M}x{MLP_H}x{MLP_F}"))
        records.append({"type": "mlp_cpu", "impl": name, "m": MLP_M,
                        "h": MLP_H, "f": MLP_F, "cpu_us": us, "rel_err": err})
    return rows


def _analytic_pricing(records):
    """Per-GEMM dtype pricing on tpu_v5e for real registry configs, decode
    and train modes — the §VI-style roofline with dtype_bytes as an axis."""
    from repro.configs.base import DECODE_32K, TRAIN_4K
    from repro.configs.registry import get_config
    from repro.core.advisor import precision_plan
    from repro.core.hardware import get_hardware

    rows = []
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape in (DECODE_32K, TRAIN_4K):
            plan = precision_plan(cfg, shape=shape, hw=get_hardware(HW))
            # model_gemms enumerates per layer: collapse identical shapes
            # (the stack repeats one block) so the report stays readable
            uniq = {}
            for g in plan:
                k = (g["m"], g["k"], g["n"], g["bound"],
                     g["recommended_dtype"])
                if k in uniq:
                    uniq[k]["count"] += 1
                else:
                    uniq[k] = {"type": "analytic", "arch": arch,
                               "mode": shape.mode, "count": 1, **g}
            records.extend(uniq.values())
            int8_wins = [g for g in plan if g["recommended_dtype"] == "int8"]
            best = max((g["speedup"] for g in int8_wins), default=1.0)
            rows.append((
                f"quant_sweep/pricing_{arch}_{shape.mode}", 0.0,
                f"int8_recommended={len(int8_wins)}/{len(plan)};"
                f"best_speedup={best:.2f}x"))
    return rows


def _kv_decode_smoke(records):
    from repro.kernels.flash_attention.ops import paged_decode_blocktable
    from repro.quant import quantize_kv

    rows, s_max, bs, nkv, d, b = 8, 256, 16, 2, 64, 4
    max_blocks = s_max // bs
    key = jax.random.PRNGKey(2)
    kp = jax.random.normal(key, (rows * max_blocks, bs, nkv, d)) * 0.5
    vp = jax.random.normal(jax.random.fold_in(key, 1),
                           (rows * max_blocks, bs, nkv, d)) * 0.5
    q = jax.random.normal(jax.random.fold_in(key, 2), (b, nkv * 4, d))
    # each row's table names its own run of consecutive blocks
    tables = (jnp.arange(b, dtype=jnp.int32)[:, None] * max_blocks
              + jnp.arange(max_blocks, dtype=jnp.int32)[None, :])
    lens = jnp.full((b,), s_max, jnp.int32)
    kq, ks = quantize_kv(kp)
    vq, vs = quantize_kv(vp)

    def f32(q):
        return paged_decode_blocktable(q, kp, vp, tables, lens,
                                       interpret=True)

    def int8(q):
        return paged_decode_blocktable(q, kq, vq, tables, lens, k_scale=ks,
                                       v_scale=vs, interpret=True)

    f32_us = wall_us(f32, q, iters=2, warmup=1, jit=False)
    int8_us = wall_us(int8, q, iters=2, warmup=1, jit=False)
    want, got = np.asarray(f32(q)), np.asarray(int8(q))
    err = float(np.abs(got - want).max() / np.abs(want).max())
    records.append({"type": "kv_cpu", "f32_us": f32_us, "int8_us": int8_us,
                    "rel_err": err, "slots": rows, "s_max": s_max,
                    "nkv": nkv, "d": d, "block_size": bs})
    return [("quant_sweep/kv_decode_f32", round(f32_us, 1),
             f"pool={rows}x{s_max}x{nkv}x{d}/block{bs}"),
            ("quant_sweep/kv_decode_int8", round(int8_us, 1),
             f"rel_err={err:.4f}")]


def _kv_slots(records):
    """Serving economics: KV slots per GiB of pool at max_seq tokens."""
    from repro.configs.registry import get_config
    from repro.quant import kv_bytes_per_token

    rows = []
    max_seq = 4096
    for arch in ARCHS:
        cfg = get_config(arch)
        d = cfg.d_model // cfg.num_heads
        per = {dt: kv_bytes_per_token(cfg.num_kv_heads, d, dt)
               * cfg.num_layers * max_seq for dt in ("auto", "int8")}
        # float slots: big models fit O(1) max_seq slots per GiB and integer
        # truncation would fake the gain
        slots = {dt: (1 << 30) / b for dt, b in per.items()}
        ratio = per["auto"] / per["int8"]
        rows.append((f"quant_sweep/kv_slots_{arch}", 0.0,
                     f"auto={slots['auto']:.1f};int8={slots['int8']:.1f};"
                     f"gain={ratio:.2f}x"))
        records.append({"type": "kv_slots", "arch": arch, "max_seq": max_seq,
                        "slots_per_gib_auto": round(slots["auto"], 2),
                        "slots_per_gib_int8": round(slots["int8"], 2),
                        "gain": ratio})
    return rows


def _summary(records) -> dict:
    analytic = [r for r in records if r["type"] == "analytic"]
    decode_int8 = [r["speedup"] for r in analytic
                   if r["mode"] == "decode" and
                   r["recommended_dtype"] == "int8"]
    gemm = {r["impl"]: r for r in records if r["type"] == "gemm_cpu"}
    kv = next(r for r in records if r["type"] == "kv_cpu")
    return {
        "hw": HW,
        "analytic": {
            "gemms_priced": sum(r["count"] for r in analytic),
            "decode_int8_recommended": sum(
                r["count"] for r in analytic
                if r["mode"] == "decode" and
                r["recommended_dtype"] == "int8"),
            "decode_int8_best_speedup": max(decode_int8, default=1.0),
            "decode_int8_min_speedup": min(decode_int8, default=1.0),
        },
        "cpu_smoke": {
            "interpret_mode": True,
            "int8_gemm_rel_err": gemm["int8"]["rel_err"],
            "fp8_gemm_rel_err": gemm["fp8_e4m3"]["rel_err"],
            "kv_decode_int8_rel_err": kv["rel_err"],
        },
        "kv_slots_per_gib": {
            r["arch"]: {"auto": r["slots_per_gib_auto"],
                        "int8": r["slots_per_gib_int8"],
                        "gain": r["gain"]}
            for r in records if r["type"] == "kv_slots"},
    }


def run(jsonl_path=None, json_path=None):
    records = []
    rows = []
    rows += _gemm_smoke(records)
    rows += _mlp_smoke(records)
    rows += _analytic_pricing(records)
    rows += _kv_decode_smoke(records)
    rows += _kv_slots(records)
    summary = _summary(records)
    rows.append((
        "quant_sweep/summary", 0.0,
        f"decode_int8_best={summary['analytic']['decode_int8_best_speedup']:.2f}x;"
        f"int8_rel_err={summary['cpu_smoke']['int8_gemm_rel_err']:.4f}"))
    if jsonl_path:
        with open(jsonl_path, "w") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(summary, f, indent=2, sort_keys=True)
            f.write("\n")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--jsonl", default=None,
                    help="per-cell records for benchmarks.report --quant")
    ap.add_argument("--json", default=None,
                    help="summary the docs quote (BENCH_quant.json)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for name, us, derived in run(args.jsonl, args.json):
        print(f"{name},{us},{derived}")


if __name__ == "__main__":
    main()
