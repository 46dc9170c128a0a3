"""Explicit-EP (shard_map) MoE dispatch must match the auto-SPMD path, in
loss AND in gradients, on a real multi-device mesh."""
import subprocess
import sys
import textwrap
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]


def _run(code: str, timeout=560):
    full = ("import os\n"
            "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'\n"
            "import sys; sys.path.insert(0, 'src')\n" + textwrap.dedent(code))
    r = subprocess.run([sys.executable, "-c", full], capture_output=True,
                       text=True, cwd=REPO_ROOT, timeout=timeout)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    return r.stdout


def test_shardmap_dispatch_matches_auto_loss_and_grads():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs.registry import get_smoke_config
        from repro.configs.base import MeshConfig
        from repro.models import init_lm, lm_loss
        from repro.parallel import sharding as sh

        # drop-free capacity so both paths route identically
        cfg = dataclasses.replace(get_smoke_config('deepseek-v3-671b'),
                                  moe_capacity_factor=8.0)
        mesh = sh.make_mesh(MeshConfig(data=2, model=4))
        sh.set_activation_context(('data',), mesh=mesh)
        params = init_lm(jax.random.PRNGKey(0), cfg)
        pspecs = sh.param_specs(params, cfg, mesh)
        params_d = jax.device_put(params, sh.to_shardings(pspecs, mesh))
        batch = {'tokens': jax.random.randint(jax.random.PRNGKey(1), (4, 32),
                                              0, cfg.vocab_size)}
        batch['labels'] = batch['tokens']
        cfg_sm = dataclasses.replace(cfg, moe_dispatch='shard_map')

        def loss(c):
            return jax.jit(lambda p, b: lm_loss(p, b, c)[0])

        with mesh:
            l_auto = float(loss(cfg)(params_d, batch))
            l_sm = float(loss(cfg_sm)(params_d, batch))
            g_auto = jax.jit(jax.grad(lambda p: lm_loss(p, batch, cfg)[0]))(params_d)
            g_sm = jax.jit(jax.grad(lambda p: lm_loss(p, batch, cfg_sm)[0]))(params_d)
        assert abs(l_auto - l_sm) < 2e-3, (l_auto, l_sm)
        errs = [float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(g_auto), jax.tree.leaves(g_sm))]
        assert max(errs) < 5e-3, max(errs)
        print('SHARDMAP_GRADS_OK', l_auto, max(errs))
    """)
    assert "SHARDMAP_GRADS_OK" in out
