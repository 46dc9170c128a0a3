"""Distribution tests on an 8-device host mesh (subprocess — the main test
process keeps 1 device)."""
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

import jax
from jax.sharding import PartitionSpec as P

from repro.configs.registry import get_smoke_config
from repro.models import init_lm
from repro.parallel import sharding as sh

REPO_ROOT = Path(__file__).resolve().parents[1]


def _run(code: str, timeout=560):
    full = ("import os\n"
            "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'\n"
            "import sys; sys.path.insert(0, 'src')\n" + textwrap.dedent(code))
    r = subprocess.run([sys.executable, "-c", full], capture_output=True,
                       text=True, cwd=REPO_ROOT, timeout=timeout)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    return r.stdout


class TestParamSpecs:
    """Spec assignment is checkable without a multi-device runtime."""

    @pytest.mark.parametrize("arch", ["internlm2-1.8b", "deepseek-v3-671b",
                                      "mamba2-780m", "zamba2-2.7b",
                                      "whisper-small"])
    def test_specs_cover_every_leaf(self, arch):
        cfg = get_smoke_config(arch)
        params = jax.eval_shape(lambda k: init_lm(k, cfg),
                                jax.random.PRNGKey(0))
        mesh = jax.sharding.Mesh(
            __import__("numpy").array(jax.devices()[:1]).reshape(1, 1),
            ("data", "model"))
        specs = sh.param_specs(params, cfg, mesh)
        n_leaves = len(jax.tree.leaves(params))
        n_specs = len(jax.tree.leaves(specs,
                                      is_leaf=lambda x: isinstance(x, P)))
        assert n_specs == n_leaves
        # rank compatibility: spec never longer than leaf rank
        flat_p = jax.tree.leaves(params)
        flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        for leaf, spec in zip(flat_p, flat_s):
            assert len(spec) <= leaf.ndim, (leaf.shape, spec)

    def test_moe_experts_sharded_on_model(self):
        cfg = get_smoke_config("deepseek-v3-671b")
        params = jax.eval_shape(lambda k: init_lm(k, cfg),
                                jax.random.PRNGKey(0))
        mesh = jax.sharding.Mesh(
            __import__("numpy").array(jax.devices()[:1]).reshape(1, 1),
            ("data", "model"))
        specs = sh.param_specs(params, cfg, mesh)
        seg1 = specs["seg1"]  # MoE segment
        assert seg1["moe"]["w_up"][1] == "model"  # (L, E, h, f): E on model


class TestMultiDevice:
    def test_train_step_parity_single_vs_mesh(self):
        """Same seed, same data: loss on a (2, 4) mesh must equal the
        single-device loss (SPMD correctness end-to-end)."""
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs.base import TrainConfig, ShapeConfig, MeshConfig
            from repro.configs.registry import get_smoke_config
            from repro.models import init_lm
            from repro.optim.adamw import init_opt
            from repro.train.train_step import make_train_step
            from repro.data.pipeline import make_batch
            from repro.parallel import sharding as sh

            cfg = get_smoke_config('internlm2-1.8b')
            tc = TrainConfig(total_steps=10, warmup_steps=1)
            shape = ShapeConfig('t', 32, 8, 'train')
            key = jax.random.PRNGKey(0)

            def run(mesh_cfg):
                params = init_lm(key, cfg)
                opt = init_opt(params, tc)
                if mesh_cfg:
                    mesh = sh.make_mesh(mesh_cfg)
                    sh.set_activation_context(('data',))
                    pspecs = sh.param_specs(params, cfg, mesh)
                    params = jax.device_put(params, sh.to_shardings(pspecs, mesh))
                    om = sh.param_specs(opt.m, cfg, mesh)
                    ov = sh.param_specs(opt.v, cfg, mesh)
                    opt = type(opt)(opt.step,
                                    jax.device_put(opt.m, sh.to_shardings(om, mesh)),
                                    jax.device_put(opt.v, sh.to_shardings(ov, mesh)))
                    bspec = sh.batch_specs(cfg, mesh)
                    ctx = mesh
                else:
                    sh.clear_activation_context()
                    bspec = None
                    import contextlib; ctx = contextlib.nullcontext()
                step = jax.jit(make_train_step(cfg, tc, batch_spec=bspec),
                               donate_argnums=(0, 1))
                losses = []
                with ctx:
                    for i in range(3):
                        batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, shape, i).items()}
                        params, opt, m = step(params, opt, batch)
                        losses.append(float(m['loss']))
                return losses

            l1 = run(None)
            l2 = run(MeshConfig(data=2, model=4))
            print('single:', l1)
            print('mesh:  ', l2)
            assert np.allclose(l1, l2, atol=2e-3), (l1, l2)
            print('PARITY_OK')
        """)
        assert "PARITY_OK" in out

    def test_decode_on_mesh(self):
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs.registry import get_smoke_config
            from repro.configs.base import MeshConfig
            from repro.models import init_lm, init_caches
            from repro.serving.serve_step import make_prefill_step, make_decode_step
            from repro.parallel import sharding as sh

            cfg = get_smoke_config('internlm2-1.8b')
            params = init_lm(jax.random.PRNGKey(0), cfg)
            mesh = sh.make_mesh(MeshConfig(data=2, model=4))
            sh.set_activation_context(('data',))
            pspecs = sh.param_specs(params, cfg, mesh)
            params_m = jax.device_put(params, sh.to_shardings(pspecs, mesh))
            toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
            prefill = jax.jit(make_prefill_step(cfg, 24))
            decode = jax.jit(make_decode_step(cfg))
            with mesh:
                logits, caches = prefill(params_m, {'tokens': toks})
                tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
                logits2, caches = decode(params_m, tok, caches, jnp.asarray(16, jnp.int32))
            # single-device reference
            sh.clear_activation_context()
            l_ref, c_ref = jax.jit(make_prefill_step(cfg, 24))(params, {'tokens': toks})
            t_ref = jnp.argmax(l_ref, -1)[:, None].astype(jnp.int32)
            l2_ref, _ = jax.jit(make_decode_step(cfg))(params, t_ref, c_ref, jnp.asarray(16, jnp.int32))
            assert np.allclose(np.asarray(logits2, np.float32),
                               np.asarray(l2_ref, np.float32), atol=2e-3)
            print('DECODE_MESH_OK')
        """)
        assert "DECODE_MESH_OK" in out

    def test_elastic_checkpoint_reshape(self):
        """Save on a (2,4) mesh, restore onto (4,2) — elastic restart."""
        out = _run("""
            import tempfile, jax, jax.numpy as jnp, numpy as np
            from repro.configs.base import MeshConfig, TrainConfig
            from repro.configs.registry import get_smoke_config
            from repro.models import init_lm
            from repro.checkpoint.ckpt import Checkpointer
            from repro.parallel import sharding as sh

            cfg = get_smoke_config('internlm2-1.8b')
            params = init_lm(jax.random.PRNGKey(0), cfg)
            mesh_a = sh.make_mesh(MeshConfig(data=2, model=4))
            pa = jax.device_put(params, sh.to_shardings(sh.param_specs(params, cfg, mesh_a), mesh_a))
            with tempfile.TemporaryDirectory() as d:
                ck = Checkpointer(d)
                ck.save(1, pa)
                mesh_b = sh.make_mesh(MeshConfig(data=4, model=2))
                restored, _, step = ck.restore(params)
                pb = jax.device_put(restored, sh.to_shardings(sh.param_specs(params, cfg, mesh_b), mesh_b))
                for x, y in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
                    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
            print('ELASTIC_OK')
        """)
        assert "ELASTIC_OK" in out
