"""Fused vs unfused MLP sweep across aligned and 8h/3-misaligned d_ff.

The paper's §VII-B case study: the SwiGLU 8h/3 heuristic lands d_ff off the
tile lattice and every MLP GEMM pays padding.  This sweep crosses that
alignment axis with the execution strategy the new linear-execution layer
dispatches between:

  jnp       XLA x @ w pair + elementwise (the pre-refactor baseline)
  unfused   two Pallas matmul kernels + XLA silu*mul (kernels/matmul)
  fused     ONE Pallas kernel for the gate/up pair + combine
            (kernels/fused_mlp), forward and — in the grad rows — its
            recompute-based custom-VJP backward

On this CPU container the Pallas rows run in interpret mode, so absolute
times are not TPU times; the signals are (a) the aligned-vs-misaligned
ratio within an impl (tile padding) and (b) fused-vs-unfused on equal
shapes (one streamed x pass + no HBM round-trip for the gate/up
activations).  On a TPU the kernels compile and the same sweep gives
deployment numbers.

Emits harness CSV rows and, with --jsonl, records that `benchmarks.report`
renders into the MLP-fusion section.

    PYTHONPATH=src python -m benchmarks.run --only mlp_fusion
    PYTHONPATH=src python -m benchmarks.mlp_fusion_sweep --jsonl mlp_fusion.jsonl
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from .common import wall_us

M, H = 256, 256  # tokens x model width
# 8h/3 for h=256 is 682.67: the heuristic's 683 breaks the 128 lane grid;
# the advisor-style re-search picks the aligned 768
DFFS = [
    ("aligned_768", 768, True),
    ("heuristic_683", 683, False),
]
IMPLS = ("jnp", "unfused", "fused")


def _hidden_fns(wg, wu):
    from repro.kernels.fused_mlp.ops import fused_mlp_hidden
    from repro.models.linear import linear

    @jax.jit
    def jnp_hidden(x):
        return jax.nn.silu(x @ wg) * (x @ wu)

    def unfused_hidden(x):
        # the model's unfused Pallas path (linear carries the custom VJP the
        # grad rows differentiate through)
        return jax.nn.silu(linear(x, wg, impl="pallas")) * \
            linear(x, wu, impl="pallas")

    def fused_hidden(x):
        return fused_mlp_hidden(x, wg, wu, mlp_type="swiglu", interpret=True)

    return {"jnp": jnp_hidden, "unfused": unfused_hidden,
            "fused": fused_hidden}


def _cell(d_ff: int):
    from repro.kernels.matmul.ops import alignment_report

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (M, H), jnp.float32)
    wg = jax.random.normal(jax.random.fold_in(key, 1), (H, d_ff)) * 0.1
    wu = jax.random.normal(jax.random.fold_in(key, 2), (H, d_ff)) * 0.1
    fns = _hidden_fns(wg, wu)
    util = alignment_report(M, H, d_ff, dtype=x.dtype)["mxu_utilization"]

    out = {}
    for impl, fn in fns.items():
        fwd = wall_us(fn, x, iters=2, warmup=1, jit=False)
        grad = wall_us(
            jax.jit(jax.grad(lambda x, fn=fn: fn(x).astype(jnp.float32).sum())),
            x, iters=2, warmup=1, jit=False)
        out[impl] = {"fwd_us": fwd, "grad_us": grad}
    return out, util


def run(jsonl_path=None):
    rows, records = [], []
    for tag, d_ff, aligned in DFFS:
        cells, util = _cell(d_ff)
        for impl in IMPLS:
            c = cells[impl]
            ratio = c["fwd_us"] / max(cells["unfused"]["fwd_us"], 1e-9)
            rows.append((
                f"mlp_fusion_sweep/{impl}_{tag}", round(c["fwd_us"], 1),
                f"grad_us={c['grad_us']:.1f};util={util:.3f};"
                f"vs_unfused={ratio:.2f}"))
            records.append({"impl": impl, "shape": tag, "d_ff": d_ff,
                            "aligned": aligned, "m": M, "h": H,
                            "mxu_utilization": util,
                            "fwd_us": c["fwd_us"], "grad_us": c["grad_us"],
                            "fwd_vs_unfused": ratio})
    # the co-design headline: what the heuristic d_ff costs each impl
    by = {(r["impl"], r["aligned"]): r["fwd_us"] for r in records}
    for impl in IMPLS:
        if by.get((impl, True)):
            ratio = by[(impl, False)] / by[(impl, True)]
            rows.append((f"mlp_fusion_sweep/{impl}_misalign_ratio", 0.0,
                         f"{ratio:.2f}x"))
            for r in records:
                if r["impl"] == impl:
                    r["misalign_ratio"] = ratio
    if jsonl_path:
        with open(jsonl_path, "w") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--jsonl", default=None,
                    help="also write per-cell records for benchmarks.report")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for name, us, derived in run(args.jsonl):
        print(f"{name},{us},{derived}")


if __name__ == "__main__":
    main()
