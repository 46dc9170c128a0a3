"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b --smoke \
        --steps 100 --global-batch 32 --seq-len 256 --data 1 --model 1

Fault-tolerance behavior (DESIGN.md §5):
  * checkpoints every `--checkpoint-every` steps (async host write),
  * `--resume` restores the latest checkpoint and continues from its step —
    because the data pipeline is a pure function of (seed, step), a restart
    (or a replacement node) regenerates exactly the batches it would have
    seen, with no data-state handoff,
  * the mesh is rebuilt from the *current* device topology at startup, and
    restore reshards the loaded leaves onto it (elastic restart).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from .. import obs
from ..configs.base import MeshConfig, ShapeConfig, TrainConfig
from ..configs.registry import get_config, get_smoke_config
from ..checkpoint.ckpt import Checkpointer
from ..core import advisor
from ..data.pipeline import make_batch
from ..models import init_lm
from ..optim.adamw import init_opt
from ..parallel import sharding as sh
from ..train.train_step import make_train_step, num_microbatches
from .compile_cache import enable_compile_cache


def build(args):
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.attn_impl:
        cfg = dataclasses.replace(cfg, attn_impl=args.attn_impl)
    if args.linear_impl:
        cfg = dataclasses.replace(cfg, linear_impl=args.linear_impl)
    mesh_cfg = MeshConfig(data=args.data, model=args.model)
    shape = ShapeConfig("cli", args.seq_len, args.global_batch, "train")
    tc = TrainConfig(total_steps=args.steps, warmup_steps=max(args.steps // 20, 1),
                     learning_rate=args.lr, optimizer=args.optimizer,
                     remat=args.remat, checkpoint_every=args.checkpoint_every,
                     checkpoint_dir=args.checkpoint_dir, seed=args.seed)
    return cfg, mesh_cfg, shape, tc


def shard_state(params, opt, cfg, mesh):
    """Place params and AdamW moments by the FSDP/TP rules of
    `parallel.sharding` (the moments follow their params' specs)."""
    params = jax.device_put(
        params, sh.to_shardings(sh.param_specs(params, cfg, mesh), mesh))
    opt = type(opt)(
        jax.device_put(opt.step),
        jax.device_put(opt.m, sh.to_shardings(sh.param_specs(opt.m, cfg, mesh),
                                              mesh)),
        jax.device_put(opt.v, sh.to_shardings(sh.param_specs(opt.v, cfg, mesh),
                                              mesh)))
    return params, opt


def jit_train_step(cfg, tc, *, n_micro: int = 1, mesh=None):
    """The jitted train step, params and optimizer state donated; on a
    mesh each microbatch is pinned to the data-parallel batch specs."""
    bspec = sh.batch_specs(cfg, mesh) if mesh is not None else None
    step_fn = make_train_step(cfg, tc, n_micro=n_micro, batch_spec=bspec)
    return jax.jit(step_fn, donate_argnums=(0, 1))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "adamw8bit"])
    ap.add_argument("--remat", default="none", choices=["none", "full", "dots"])
    ap.add_argument("--attn-impl", default=None,
                    choices=[None, "naive", "blocked", "flash"])
    ap.add_argument("--linear-impl", default=None,
                    choices=[None, "jnp", "pallas", "tuned", "fused"],
                    help="dispatch for every dense projection GEMM "
                         "(repro.models.linear); fused = Pallas fused "
                         "SwiGLU/MLP kernel + tuned matmuls")
    ap.add_argument("--microbatch", type=int, default=0, help="per-device rows; 0=no accumulation")
    ap.add_argument("--checkpoint-every", type=int, default=50,
                    help="steps between checkpoints; 0 = never checkpoint "
                         "(not even at the end)")
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg, mesh_cfg, shape, tc = build(args)

    # shape-rule report (the paper's contribution, surfaced at launch)
    findings = advisor.check_alignment(cfg, tp=mesh_cfg.model,
                                       global_batch=shape.global_batch)
    for f in findings:
        if f.severity != "ok":
            print(f"[advisor:{f.severity}] {f.rule}: {f.message}")

    use_mesh = mesh_cfg.num_devices > 1
    if use_mesh:
        assert len(jax.devices()) >= mesh_cfg.num_devices, (
            f"need {mesh_cfg.num_devices} devices, have {len(jax.devices())}")
        mesh = sh.make_mesh(mesh_cfg)
        sh.set_activation_context(("data",))
    else:
        mesh = None

    if args.microbatch:
        tc = dataclasses.replace(tc, microbatch_per_device=args.microbatch)
        n_micro = num_microbatches(shape, mesh_cfg, tc)
    else:
        n_micro = 1

    key = jax.random.PRNGKey(tc.seed)
    params = init_lm(key, cfg)
    opt = init_opt(params, tc)
    start_step = 0
    ck = (Checkpointer(tc.checkpoint_dir, keep=3)
          if tc.checkpoint_every or args.resume else None)
    if args.resume and ck.latest_step() is not None:
        params_np, opt_np, start_step = ck.restore(params, opt)
        params = jax.tree.map(jnp.asarray, params_np)
        opt = jax.tree.map(jnp.asarray, opt_np)
        print(f"resumed from step {start_step}")

    if use_mesh:
        params, opt = shard_state(params, opt, cfg, mesh)
    step_fn = jit_train_step(cfg, tc, n_micro=n_micro, mesh=mesh)

    ctx = mesh if use_mesh else _null()
    t0 = time.time()
    tokens_done = 0
    with ctx:
        for step in range(start_step, tc.total_steps):
            with obs.span("train_step", cat="train", step=step):
                batch = {k: jnp.asarray(v)
                         for k, v in make_batch(cfg, shape, step,
                                                tc.seed).items()}
                params, opt, metrics = step_fn(params, opt, batch)
            if obs.enabled():
                obs.counter("train.steps").inc()
                obs.counter("train.tokens").inc(
                    shape.global_batch * shape.seq_len)
            tokens_done += shape.global_batch * shape.seq_len
            if step % args.log_every == 0 or step == tc.total_steps - 1:
                loss = float(metrics["loss"])
                dt = time.time() - t0
                print(f"step {step:5d}  loss {loss:.4f}  "
                      f"grad_norm {float(metrics['grad_norm']):.3f}  "
                      f"tok/s {tokens_done/max(dt,1e-6):,.0f}", flush=True)
            if tc.checkpoint_every and step and step % tc.checkpoint_every == 0:
                ck.save(step, params, opt, meta={"arch": cfg.name}, blocking=False)
    if tc.checkpoint_every:
        ck.save(tc.total_steps, params, opt, meta={"arch": cfg.name})
        ck.wait()
    print("done")


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
