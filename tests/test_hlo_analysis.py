"""HLO structural analyzer: trip-count multiplication, dot FLOPs,
collective byte census — validated against a known jit program."""
import jax
import jax.numpy as jnp
import pytest

from repro.core.hlo_analysis import analyze_hlo, parse_module


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


class TestAnalyzer:
    def test_plain_matmul_flops_exact(self):
        m, k, n = 128, 256, 64
        a = jnp.zeros((m, k), jnp.float32)
        b = jnp.zeros((k, n), jnp.float32)
        txt = _hlo(lambda a, b: a @ b, a, b)
        c = analyze_hlo(txt)
        assert c.flops == pytest.approx(2 * m * k * n, rel=1e-6)
        assert c.dots >= 1

    def test_scan_multiplies_by_trip_count(self):
        m = 64
        w = jnp.zeros((8, m, m), jnp.float32)  # 8 scanned layers

        def f(x, w):
            def body(h, wi):
                return jnp.tanh(h @ wi), None
            h, _ = jax.lax.scan(body, x, w)
            return h

        txt = _hlo(f, jnp.zeros((4, m)), w)
        c = analyze_hlo(txt)
        want = 8 * 2 * 4 * m * m  # trips x dot flops
        assert c.flops == pytest.approx(want, rel=0.01)
        assert 8 in c.loops.values()

    def test_nested_scan(self):
        m = 32
        w = jnp.zeros((3, 5, m, m), jnp.float32)

        def f(x, w):
            def outer(h, wo):
                def inner(h2, wi):
                    return h2 @ wi, None
                h, _ = jax.lax.scan(inner, h, wo)
                return h, None
            h, _ = jax.lax.scan(outer, x, w)
            return h

        txt = _hlo(f, jnp.zeros((2, m)), w)
        c = analyze_hlo(txt)
        want = 15 * 2 * 2 * m * m
        assert c.flops == pytest.approx(want, rel=0.01)

    def test_bytes_positive_and_reasonable(self):
        a = jnp.zeros((256, 256), jnp.float32)
        txt = _hlo(lambda a: jnp.tanh(a) + 1.0, a)
        c = analyze_hlo(txt)
        nbytes = 256 * 256 * 4
        assert nbytes <= c.bytes <= 6 * nbytes

    def test_parse_module_finds_entry(self):
        txt = _hlo(lambda x: x * 2, jnp.zeros((4,)))
        comps, entry = parse_module(txt)
        assert entry is not None and entry in comps


class TestCollectiveCensus:
    def test_psum_counted_as_all_reduce(self):
        import subprocess
        import sys
        import textwrap
        from pathlib import Path
        # collectives need >1 device: run in a subprocess with 4 host devices
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P, NamedSharding
            import sys
            sys.path.insert(0, "src")
            from repro.core.hlo_analysis import analyze_hlo
            mesh = jax.make_mesh((4,), ("d",),
                                 axis_types=(jax.sharding.AxisType.Auto,))
            s = NamedSharding(mesh, P("d", None))
            x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
            def f(x):
                return jnp.sum(x @ x.T)
            txt = jax.jit(f, in_shardings=s).lower(x).compile().as_text()
            c = analyze_hlo(txt)
            assert c.coll_total > 0, "expected collective traffic"
            print("COLL_OK", c.coll_total)
        """)
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, cwd=Path(__file__).resolve().parents[1],
                           timeout=300)
        assert "COLL_OK" in r.stdout, r.stdout + r.stderr
