"""Pipeline parallelism: GPipe schedule over a host-device mesh axis must
reproduce the sequential layer stack exactly."""
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


def test_pipeline_matches_sequential():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.pipeline import pipeline_apply, split_layers_into_stages

        L, S, M, B, D = 8, 4, 6, 2, 16   # layers, stages, microbatches
        mesh = jax.make_mesh((S, 2), ("pod", "data"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (L, D, D)) * (0.5 / D ** 0.5)
        x = jax.random.normal(jax.random.fold_in(key, 1), (M, B, D))

        def layer(wi, h):
            return jnp.tanh(h @ wi)

        def stage_fn(params, h):   # params: (L/S, D, D)
            def body(h, wi):
                return layer(wi, h), None
            h, _ = jax.lax.scan(body, h, params)
            return h

        # sequential reference
        def seq(x1):
            def body(h, wi):
                return layer(wi, h), None
            h, _ = jax.lax.scan(body, x1, w)
            return h
        want = jax.vmap(seq)(x)

        staged = split_layers_into_stages({"w": w}, S)["w"]
        got = pipeline_apply(stage_fn, staged, x, mesh, axis="pod")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
        print("PIPELINE_OK")
    """)
    assert "PIPELINE_OK" in out


def test_pipeline_rejects_indivisible_layers():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.parallel.pipeline import split_layers_into_stages
        try:
            split_layers_into_stages({"w": jnp.zeros((7, 4, 4))}, 2)
            print("NO_ERROR")
        except AssertionError as e:
            print("RULE_ENFORCED", "paper" in str(e))
    """)
    assert "RULE_ENFORCED True" in out
