"""Training step-time sweep over attention impls and shape alignment.

The paper's headline claim is about *training* throughput: tile-aligned
model shapes keep the attention kernels on their fast paths.  This sweep
reproduces that end-to-end — a full `train.train_step` (value_and_grad +
AdamW) on a small LM — crossing:

  attn_impl  naive | blocked | flash   (flash = the Pallas kernel pair with
                                        its custom-VJP fused backward)
  shape      aligned (head_dim 64, seq a block multiple) vs
             unaligned (head_dim 80, seq off the 128 grid — the GPT-3 2.7B
             pathology of paper Fig. 1)

On this CPU container the flash rows run the kernels in Pallas interpret
mode, so absolute times are not TPU times; the aligned-vs-unaligned *ratio*
within an impl is the signal (padding + masked tail work), and on a TPU,
where the kernels compile, the same sweep yields deployment numbers.

Emits harness CSV rows and, with --jsonl, records that `benchmarks.report`
renders into the training-attention section.

    PYTHONPATH=src python -m benchmarks.run --only train_attention
    PYTHONPATH=src python -m benchmarks.train_attention_sweep --jsonl train_attention.jsonl
"""
from __future__ import annotations

import argparse
import json

import jax

from .common import wall_us

IMPLS = ("naive", "blocked", "flash")
# (tag, seq, head_dim, aligned): aligned keeps both seq and head_dim on the
# (sublane, lane) grid; unaligned breaks both (the paper's h/a = 80 case)
SHAPES = [
    ("aligned_s256_d64", 256, 64, True),
    ("unaligned_s200_d80", 200, 80, False),
]
BATCH = 2


def _cell(seq: int, head_dim: int, impl: str):
    from repro.configs.base import ModelConfig, TrainConfig
    from repro.models import init_lm
    from repro.optim.adamw import init_opt
    from repro.train.train_step import make_train_step

    cfg = ModelConfig(name=f"sweep_{impl}", family="dense", num_layers=2,
                      d_model=4 * head_dim, num_heads=4, num_kv_heads=2,
                      d_ff=2 * 4 * head_dim, vocab_size=512,
                      head_dim=head_dim, attn_impl=impl, attn_block_kv=128,
                      dtype="float32")
    tc = TrainConfig(total_steps=4, warmup_steps=1)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    opt = init_opt(params, tc)
    step = make_train_step(cfg, tc)
    key = jax.random.PRNGKey(1)
    batch = {
        "tokens": jax.random.randint(key, (BATCH, seq), 0, cfg.vocab_size),
        "labels": jax.random.randint(jax.random.fold_in(key, 1),
                                     (BATCH, seq), 0, cfg.vocab_size),
    }

    def one_step(params, opt, batch):
        p, o, metrics = step(params, opt, batch)
        return metrics["loss"]

    us = wall_us(one_step, params, opt, batch, iters=2, warmup=1)
    loss = float(one_step(params, opt, batch))
    return us, loss


def run(jsonl_path=None):
    rows, records = [], []
    for tag, seq, head_dim, aligned in SHAPES:
        for impl in IMPLS:
            us, loss = _cell(seq, head_dim, impl)
            rows.append((f"train_attention_sweep/{impl}_{tag}", round(us, 1),
                         f"loss={loss:.3f};aligned={int(aligned)}"))
            records.append({"impl": impl, "shape": tag, "seq": seq,
                            "head_dim": head_dim, "aligned": aligned,
                            "us_per_step": us, "loss": loss})
    # the co-design headline: what misalignment costs each impl
    by = {(r["impl"], r["aligned"]): r["us_per_step"] for r in records}
    for impl in IMPLS:
        if (impl, True) in by and (impl, False) in by and by[(impl, True)]:
            ratio = by[(impl, False)] / by[(impl, True)]
            rows.append((f"train_attention_sweep/{impl}_misalign_ratio",
                         0.0, f"{ratio:.2f}x"))
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
