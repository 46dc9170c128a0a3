"""Smoke run on a TPU: the serving engine and the train step at
internlm2-1.8b widths, with every Pallas kernel on those paths compiled.

    python3 chip_smoke.py              # one chip: phases `serve` and `train`
    python3 chip_smoke.py --chips 4    # four chips: phase `mesh` only

Phase `serve` serves synthetic requests with the full 24-layer model through
the continuous-batching engine (block-table KV pool with prefix caching, the
Pallas paged-decode kernel, bf16 compute).  It checks that every completion
is ok, that the decode program holds a compiled kernel (`tpu_custom_call`),
and that one decode step through the kernel matches the same engine's
decode with plain jnp attention on the same pool.

Phase `train` takes 3 steps of the launcher's train step (flash attention,
fused MLP, full remat) at internlm2-1.8b widths with depth cut to fit one
chip, checks the losses are finite, and checks the first step's loss and
gradient norm against the jnp model on the same batch.

Phase `mesh` (`--chips 4`) runs one train step of the `train` model on a
data=2 x model=2 mesh and compares its loss and gradient norm with the same
step on device 0 alone.

Each phase prints one line; the last line of output is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
Without a TPU the script exits 1 and prints no result.  It keeps its files
under `.chip_smoke/`, and JAX's compile cache where
`repro.launch.compile_cache` puts it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT_DIR = ROOT / ".chip_smoke"

ARCH = "internlm2-1.8b"
SERVE_REQUESTS = 12
SERVE_PROMPT = (64, 1024)       # prompt lengths drawn in this range
SERVE_GEN = 32                  # tokens generated per request
SERVE_SLOTS = 8
# Depth cut for training, sized from `compiled.memory_analysis()` of the
# step compiled for a v5e: 4 layers need 12.4 GiB (params, grads, AdamW
# moments, activations under full remat at 4 x 2048 tokens); each further
# layer adds 0.7 GiB, and the jnp reference step runs beside the optimizer
# state: 4 layers leave room on a 16 GB chip.
TRAIN_LAYERS = 4
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 2048, 3

# Kernel vs jnp bounds.  Both sides compute in bf16 with f32 softmax and
# accumulation; they differ in summation order and in where they round.
# Decode logits measured 0.0046 (2 layers) and 0.0060 (6 layers) rel L2 at
# these widths in interpret mode; a wrong head, mask or position is O(1).
DECODE_REL_L2 = 5e-2            # ||kernel - jnp|| / ||jnp|| over live rows
LOSS_ABS = 2e-2                 # |loss - jnp loss|, loss ~ ln(vocab) ~ 11.4
GNORM_REL = 5e-2                # |gnorm - jnp gnorm| / jnp gnorm
MESH_LOSS_ABS = 2e-2            # 4-chip step vs device 0 alone
MESH_GNORM_REL = 5e-2

_COMPILE = {"s": 0.0, "n": 0}


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def report(phase: str, **fields) -> None:
    print(f"[{phase}] " + json.dumps(fields, default=str), flush=True)


def _on_event(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE["s"] += duration
        _COMPILE["n"] += 1


class Clock:
    """Wall seconds and backend-compile seconds since construction."""

    def __init__(self):
        self.t0, self.c0, self.n0 = (time.perf_counter(), _COMPILE["s"],
                                     _COMPILE["n"])

    def wall_s(self) -> float:
        return round(time.perf_counter() - self.t0, 3)

    def compile_s(self) -> float:
        return round(_COMPILE["s"] - self.c0, 3)

    def compiles(self) -> int:
        return _COMPILE["n"] - self.n0


def peak_hbm_gib():
    """Peak bytes in use on device 0 so far, where the backend reports it."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return None if peak is None else round(peak / 1024 ** 3, 3)


def rel_l2(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# --- phase serve ---------------------------------------------------------------------

def phase_serve(seed: int, kind: str) -> None:
    import jax
    import numpy as np

    from repro.configs.registry import get_config
    from repro.models import init_lm
    from repro.serving.engine import Engine, synthetic_requests
    from repro.serving.engine.engine import _make_decode_bt

    cfg = get_config(ARCH)
    clock = Clock()
    params = jax.jit(init_lm, static_argnums=1)(jax.random.PRNGKey(seed), cfg)
    eng = Engine(params, cfg, max_batch=SERVE_SLOTS,
                 max_prompt=SERVE_PROMPT[1], max_new=SERVE_GEN,
                 use_paged_kernel=True, prefix_cache=True)
    pol = eng.policy
    step_s = eng.calibrate_step_s()     # warms every bucket + decode
    warm = {"wall_s": clock.wall_s(), "compile_s": clock.compile_s(),
            "compiles": clock.compiles()}

    reqs = synthetic_requests(
        SERVE_REQUESTS, pattern="burst", min_prompt=SERVE_PROMPT[0],
        max_prompt=SERVE_PROMPT[1], min_new=SERVE_GEN, max_new=SERVE_GEN,
        vocab=cfg.vocab_size, step_s=step_s, seed=seed)
    run = Clock()
    done, stats = eng.run(reqs)
    served = {"wall_s": run.wall_s(), "compiles": run.compiles()}
    bad = [(c.rid, c.finish_reason, c.detail) for c in done if not c.ok]
    if len(done) != len(reqs) or bad:
        fail(f"serve: {len(done)}/{len(reqs)} completions, not ok: {bad}")

    # one admitted batch, one decode step two ways on the same pool: the
    # same engine program with attn_impl="naive" (jnp attention), then the
    # engine's own (paged kernel).  Each writes the step's K/V at `pos`
    # before it reads the pool, so the jnp step leaves the kernel step's
    # inputs as they were, and no copy of the pool is needed
    kernel_decode = eng._decode
    ref_decode = _make_decode_bt(dataclasses.replace(eng.cfg,
                                                     attn_impl="naive"))
    seen = {}

    def decode_both(params, tok, caches, pos, tables):
        if seen:
            return kernel_decode(params, tok, caches, pos, tables)
        seen["hlo"] = kernel_decode.lower(params, tok, caches, pos,
                                          tables).compile().as_text()
        ref_logits, caches = ref_decode(params, tok, caches, pos, tables)
        logits, caches = kernel_decode(params, tok, caches, pos, tables)
        live = np.asarray(pos) > 0
        seen["rows"] = int(live.sum())
        seen["kernel"] = np.asarray(logits, np.float32)[live]
        seen["jnp"] = np.asarray(ref_logits, np.float32)[live]
        return logits, caches

    eng._decode = decode_both
    try:
        check_reqs = [dataclasses.replace(r, max_new_tokens=2)
                      for r in reqs[:SERVE_SLOTS]]
        eng.run(check_reqs)
    finally:
        eng._decode = kernel_decode
    err = rel_l2(seen["kernel"], seen["jnp"])
    max_abs = float(np.max(np.abs(seen["kernel"] - seen["jnp"])))
    argmax_agree = float(np.mean(seen["kernel"].argmax(-1)
                                 == seen["jnp"].argmax(-1)))
    n_kernels = seen["hlo"].count("tpu_custom_call")
    report("serve", arch=cfg.name, layers=cfg.num_layers,
           d_model=cfg.d_model, heads=cfg.num_heads,
           kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
           dtype=cfg.dtype, kv_pool={"slots": pol.num_slots,
                                     "seq_max": pol.seq_max,
                                     "block_size": eng.pool.block_size},
           prompt_buckets=list(pol.prompt_buckets),
           warmup=warm, requests=len(reqs),
           prompt_tokens=sum(r.prompt_len for r in reqs),
           generated=stats.total_generated, served=served,
           ok=stats.num_ok, finish_reasons=stats.finish_reasons,
           decode_rows=seen["rows"], decode_rel_l2=err,
           decode_rel_l2_bound=DECODE_REL_L2, decode_max_abs=max_abs,
           decode_argmax_agree=argmax_agree,
           decode_tpu_custom_calls=n_kernels, peak_hbm_gib=peak_hbm_gib(),
           device_kind=kind)
    if n_kernels == 0:
        fail("serve: no tpu_custom_call in the compiled decode program")
    if not err <= DECODE_REL_L2:
        fail(f"serve: kernel vs jnp decode logits rel L2 {err} > "
             f"{DECODE_REL_L2}")
    del eng, params


# --- phase train ---------------------------------------------------------------------

def _train_setup(seed: int):
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.configs.registry import get_config

    full = get_config(ARCH)
    cfg = dataclasses.replace(full, num_layers=TRAIN_LAYERS,
                              attn_impl="flash", linear_impl="fused")
    tc = TrainConfig(total_steps=TRAIN_STEPS, warmup_steps=1, remat="full",
                     checkpoint_every=0, seed=seed)
    shape = ShapeConfig("chip_smoke", TRAIN_SEQ, TRAIN_BATCH, "train")
    return full, cfg, tc, shape


def _batch(cfg, shape, step: int, seed: int):
    import jax.numpy as jnp

    from repro.data.pipeline import make_batch
    return {k: jnp.asarray(v)
            for k, v in make_batch(cfg, shape, step, seed).items()}


def _jnp_loss_and_gnorm(cfg, tc, params, batch):
    """Loss and gradient norm of the jnp model (naive attention, XLA
    matmuls) on `batch`: the reference the kernel step is held to."""
    import jax

    from repro.models import lm_loss
    from repro.optim.adamw import global_norm
    ref_cfg = dataclasses.replace(cfg, attn_impl="naive", linear_impl="jnp")

    def f(p, b):
        (loss, _), g = jax.value_and_grad(lm_loss, has_aux=True)(
            p, b, ref_cfg, remat=tc.remat)
        return loss, global_norm(g)
    loss, gnorm = jax.jit(f)(params, batch)
    return float(loss), float(gnorm)


def phase_train(seed: int, kind: str) -> None:
    import jax

    from repro.launch.train import jit_train_step
    from repro.models import init_lm
    from repro.optim.adamw import init_opt

    full, cfg, tc, shape = _train_setup(seed)
    clock = Clock()
    params = jax.jit(init_lm, static_argnums=1)(jax.random.PRNGKey(seed), cfg)
    opt = init_opt(params, tc)
    batch = _batch(cfg, shape, 0, seed)
    ref_loss, ref_gnorm = _jnp_loss_and_gnorm(cfg, tc, params, batch)
    ref_s = clock.wall_s()

    clock = Clock()
    compiled = jit_train_step(cfg, tc).lower(params, opt, batch).compile()
    mem = compiled.memory_analysis()
    compile_wall = clock.wall_s()
    hlo_kernels = compiled.as_text().count("tpu_custom_call")
    losses, gnorms, step_s = [], [], []
    for step in range(TRAIN_STEPS):
        b = batch if step == 0 else _batch(cfg, shape, step, seed)
        t = time.perf_counter()
        params, opt, metrics = compiled(params, opt, b)
        losses.append(float(metrics["loss"]))
        gnorms.append(float(metrics["grad_norm"]))
        step_s.append(round(time.perf_counter() - t, 3))
    gb = 1024 ** 3
    report("train", arch=cfg.name,
           depth_cut=f"{full.num_layers} -> {cfg.num_layers} layers",
           d_model=cfg.d_model, heads=cfg.num_heads,
           kv_heads=cfg.num_kv_heads, d_ff=cfg.d_ff, vocab=cfg.vocab_size,
           attn_impl=cfg.attn_impl, linear_impl=cfg.linear_impl,
           remat=tc.remat, batch=[shape.global_batch, shape.seq_len],
           memory_gb={
               "arguments": round(mem.argument_size_in_bytes / gb, 3),
               "outputs": round(mem.output_size_in_bytes / gb, 3),
               "temp": round(mem.temp_size_in_bytes / gb, 3),
               "aliased": round(mem.alias_size_in_bytes / gb, 3)},
           compile_s=clock.compile_s(), compile_wall_s=compile_wall,
           jnp_reference_wall_s=ref_s, step_wall_s=step_s,
           tpu_custom_calls=hlo_kernels, losses=losses, grad_norms=gnorms,
           jnp_loss=ref_loss, jnp_grad_norm=ref_gnorm,
           loss_abs_err=abs(losses[0] - ref_loss), loss_bound=LOSS_ABS,
           gnorm_rel_err=abs(gnorms[0] - ref_gnorm) / ref_gnorm,
           gnorm_bound=GNORM_REL, peak_hbm_gib=peak_hbm_gib(),
           device_kind=kind)
    if hlo_kernels == 0:
        fail("train: no tpu_custom_call in the compiled train step")
    if not all(math.isfinite(x) for x in losses + gnorms):
        fail(f"train: non-finite loss or grad norm: {losses} {gnorms}")
    if not abs(losses[0] - ref_loss) <= LOSS_ABS:
        fail(f"train: loss {losses[0]} vs jnp {ref_loss}")
    if not abs(gnorms[0] - ref_gnorm) <= GNORM_REL * ref_gnorm:
        fail(f"train: grad norm {gnorms[0]} vs jnp {ref_gnorm}")


# --- phase mesh (four chips) ---------------------------------------------------------

def phase_mesh(seed: int, kind: str) -> None:
    """One train step on a data=2 x model=2 mesh vs device 0 alone.

    Both sides run the XLA paths (naive attention, jnp matmuls): the TPU
    compiler cannot partition a Pallas kernel across a mesh, and the model
    does not yet wrap its kernels in shard_map."""
    import jax

    from repro.configs.base import MeshConfig
    from repro.launch.train import jit_train_step, shard_state
    from repro.models import init_lm
    from repro.optim.adamw import init_opt
    from repro.parallel import sharding as sh

    _, cfg, tc, shape = _train_setup(seed)
    cfg = dataclasses.replace(cfg, attn_impl="naive", linear_impl="jnp")
    devices = jax.devices()

    def fresh():
        params = init_lm(jax.random.PRNGKey(seed), cfg)
        return params, init_opt(params, tc)

    batch = _batch(cfg, shape, 0, seed)
    clock = Clock()
    with jax.default_device(devices[0]):
        params, opt = fresh()
        _, _, single = jit_train_step(cfg, tc)(params, opt, batch)
        single = {k: float(single[k]) for k in ("loss", "grad_norm")}
    del params, opt
    single_s = clock.wall_s()

    mesh_cfg = MeshConfig(data=2, model=2)
    mesh = sh.make_mesh(mesh_cfg)
    sh.set_activation_context(("data",))
    clock = Clock()
    try:
        with jax.default_device(devices[0]):
            params, opt = fresh()
        params, opt = shard_state(params, opt, cfg, mesh)
        spread = sorted({s.device.id for leaf in jax.tree.leaves(params)
                         for s in leaf.addressable_shards})
        split = sum(1 for leaf in jax.tree.leaves(params)
                    if len({str(s.index) for s in leaf.addressable_shards}) > 1)
        with mesh:
            params, opt, sharded = jit_train_step(cfg, tc, mesh=mesh)(
                params, opt, batch)
            sharded = {k: float(sharded[k]) for k in ("loss", "grad_norm")}
        after = sorted({s.device.id for leaf in jax.tree.leaves(params)
                        for s in leaf.addressable_shards})
    finally:
        sh.clear_activation_context()
    loss_err = abs(sharded["loss"] - single["loss"])
    gnorm_err = abs(sharded["grad_norm"] - single["grad_norm"]) / \
        single["grad_norm"]
    n_leaves = len(jax.tree.leaves(params))
    report("mesh", arch=cfg.name, depth=cfg.num_layers,
           mesh={"data": mesh_cfg.data, "model": mesh_cfg.model},
           attn_impl=cfg.attn_impl, linear_impl=cfg.linear_impl,
           batch=[shape.global_batch, shape.seq_len],
           param_devices=spread, param_devices_after_step=after,
           params_split=f"{split}/{n_leaves} leaves",
           single=single, sharded=sharded, single_wall_s=single_s,
           sharded_wall_s=clock.wall_s(), compile_s=clock.compile_s(),
           loss_abs_err=loss_err, loss_bound=MESH_LOSS_ABS,
           gnorm_rel_err=gnorm_err, gnorm_bound=MESH_GNORM_REL,
           peak_hbm_gib=peak_hbm_gib(), device_kind=kind)
    if spread != sorted(d.id for d in devices) or after != spread:
        fail(f"mesh: params live on devices {spread} / {after}")
    if split == 0:
        fail("mesh: no parameter is split across devices")
    if not loss_err <= MESH_LOSS_ABS:
        fail(f"mesh: loss {sharded['loss']} vs single {single['loss']}")
    if not gnorm_err <= MESH_GNORM_REL:
        fail(f"mesh: grad norm {sharded['grad_norm']} vs single "
             f"{single['grad_norm']}")


# --- main ----------------------------------------------------------------------------

def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: phases serve + train; 4: phase mesh only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the repro package is not under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    # a tuning cache that does not exist: no stray file steers the kernels'
    # block choices, every kernel runs its default blocks
    tuning = OUT_DIR / "tuning_cache.json"
    tuning.unlink(missing_ok=True)
    os.environ["REPRO_TUNING_CACHE"] = str(tuning)

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (default device: {dev.platform})")
    count = len(jax.devices())
    if count < args.chips:
        fail(f"--chips {args.chips} but JAX sees {count} device(s)")
    print(f"chip_smoke: {count} x {dev.device_kind}, jax {jax.__version__}, "
          f"compile cache "
          f"{cache_dir or os.environ.get('JAX_COMPILATION_CACHE_DIR')}",
          flush=True)

    if args.chips == 4:
        phase_mesh(args.seed, dev.device_kind)
    else:
        phase_serve(args.seed, dev.device_kind)
        gc.collect()    # the served model's 7.6 GB go before training starts
        phase_train(args.seed, dev.device_kind)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)


if __name__ == "__main__":
    main()
