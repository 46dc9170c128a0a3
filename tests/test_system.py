"""End-to-end behaviour tests for the co-design system: the advisor's
predictions must line up with what the dry-run machinery measures, and the
full train->checkpoint->resume->serve lifecycle must hold together."""
import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ShapeConfig, TrainConfig
from repro.configs.registry import get_config, get_smoke_config, list_archs
from repro.core.hlo_analysis import analyze_hlo


def test_registry_has_all_assigned_archs():
    names = set(list_archs())
    for a in ["zamba2-2.7b", "qwen1.5-4b", "nemotron-4-340b",
              "internlm2-1.8b", "command-r-plus-104b", "deepseek-v3-671b",
              "llama4-maverick-400b-a17b", "internvl2-76b", "whisper-small",
              "mamba2-780m"]:
        assert a in names


def test_full_configs_match_nameplate_params():
    targets = {"qwen1.5-4b": 4e9, "nemotron-4-340b": 340e9,
               "internlm2-1.8b": 1.8e9, "command-r-plus-104b": 104e9,
               "deepseek-v3-671b": 671e9,
               "llama4-maverick-400b-a17b": 400e9, "mamba2-780m": 0.78e9}
    for name, t in targets.items():
        p = get_config(name).param_count()
        assert 0.85 < p / t < 1.15, (name, p / t)


def test_llama4_active_params_match_a17b():
    a = get_config("llama4-maverick-400b-a17b").active_param_count()
    assert 0.85 < a / 17e9 < 1.15


def test_advisor_prediction_agrees_with_hlo_measurement():
    """System-level closure: the advisor predicts blocked attention cannot
    change FLOPs materially but slashes attention HBM traffic; verify on a
    small jitted model that HLO bytes drop while flops stay ~equal."""
    from repro.models import init_lm, lm_loss
    cfg = get_smoke_config("internlm2-1.8b")
    cfg = dataclasses.replace(cfg, num_layers=2)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.zeros((2, 256), jnp.int32),
             "labels": jnp.zeros((2, 256), jnp.int32)}

    def measure(c):
        txt = (jax.jit(lambda p, b: lm_loss(p, b, c)[0])
               .lower(params, batch).compile().as_text())
        return analyze_hlo(txt)

    naive = measure(cfg)
    blocked = measure(dataclasses.replace(cfg, attn_impl="blocked",
                                          attn_block_kv=64))
    assert blocked.flops == pytest.approx(naive.flops, rel=0.25)
    assert blocked.bytes < naive.bytes  # the whole point of §VI-C3


def test_dryrun_single_cell_subprocess():
    """The dry-run entry point works as a CLI on the smallest cell."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "whisper-small", "--shape", "decode_32k"],
        capture_output=True, text=True, cwd=Path(__file__).resolve().parents[1],
        env={"PYTHONPATH": "src", "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
             "HOME": os.environ.get("HOME", "/tmp"), "JAX_PLATFORMS": "cpu"},
        timeout=560)
    assert '"status": "ok"' in r.stdout, r.stdout + r.stderr[-2000:]


@pytest.mark.parametrize("every,want_steps", [(0, None), (2, [2, 3])])
def test_train_cli_checkpoint_every(tmp_path, monkeypatch, every, want_steps):
    """`--checkpoint-every 0` writes no checkpoint, not even the final one;
    otherwise the periodic and the final checkpoint are both written."""
    from repro.checkpoint.ckpt import Checkpointer
    from repro.launch import train
    # a cache dir named by the environment: the launcher then sets none
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    ckpt = tmp_path / "ckpt"
    train.main(["--arch", "internlm2-1.8b", "--smoke", "--steps", "3",
                "--global-batch", "2", "--seq-len", "32",
                "--checkpoint-every", str(every), "--checkpoint-dir", str(ckpt)])
    if want_steps is None:
        assert not ckpt.exists()
    else:
        assert Checkpointer(str(ckpt)).all_steps() == want_steps


def test_train_resume_lifecycle(tmp_path):
    """Train 6 steps, kill, resume to 10 — the resumed run must produce the
    same step-10 loss as an uninterrupted run (determinism across restart)."""
    from repro.data.pipeline import make_batch
    from repro.models import init_lm
    from repro.optim.adamw import init_opt
    from repro.train.train_step import make_train_step
    from repro.checkpoint.ckpt import Checkpointer

    cfg = get_smoke_config("internlm2-1.8b")
    tc = TrainConfig(total_steps=10, warmup_steps=1)
    shape = ShapeConfig("t", 32, 4, "train")
    step_fn = jax.jit(make_train_step(cfg, tc))

    def fresh():
        p = init_lm(jax.random.PRNGKey(0), cfg)
        return p, init_opt(p, tc)

    # uninterrupted
    p, o = fresh()
    for i in range(10):
        batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, shape, i).items()}
        p, o, m = step_fn(p, o, batch)
    want = float(m["loss"])

    # interrupted at 6 + resumed
    p, o = fresh()
    ck = Checkpointer(str(tmp_path))
    for i in range(6):
        batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, shape, i).items()}
        p, o, m = step_fn(p, o, batch)
    ck.save(6, p, o)
    p2, o2 = fresh()
    p2_np, o2_np, start = ck.restore(p2, o2)
    p2 = jax.tree.map(jnp.asarray, p2_np)
    o2 = jax.tree.map(jnp.asarray, o2_np)
    for i in range(start, 10):
        batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, shape, i).items()}
        p2, o2, m2 = step_fn(p2, o2, batch)
    got = float(m2["loss"])
    assert got == pytest.approx(want, abs=1e-5)
