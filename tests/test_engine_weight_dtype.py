"""The engine's serving copy of the weights: GEMM weights cast once to the
compute dtype when the engine is built, every other leaf as given, and the
served logits and tokens bit-identical to casting in every program call."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.registry import get_smoke_config
from repro.models import apply_lm, init_caches, init_lm
from repro.models.linear import (QUANT_WEIGHT_KEYS, QuantizedLinear,
                                 cast_linear_params, quantize_linear_params)
from repro.serving.engine import Engine
from repro.serving.engine.request import Request

KEY = jax.random.PRNGKey(5)


def _cfg(arch, **kw):
    return dataclasses.replace(get_smoke_config(arch), dtype="bfloat16", **kw)


CASES = {
    "dense": lambda: _cfg("internlm2-1.8b"),
    "tied": lambda: _cfg("internlm2-1.8b", tie_embeddings=True),
    "qkv_bias": lambda: _cfg("qwen1.5-4b"),
    "layernorm": lambda: _cfg("command-r-plus-104b"),
    "mla_moe": lambda: _cfg("deepseek-v3-671b"),
    "moe_softmax": lambda: _cfg("llama4-maverick-400b-a17b"),
}
# leaves each case must hold, so that every kind of leaf is checked
MUST_HOLD = {
    "dense": {"lm_head", "wq", "w_gate", "scale"},
    "tied": {"embed", "wq"},
    "qkv_bias": {"bq", "bk", "bv"},
    "layernorm": {"bias", "scale"},
    "mla_moe": {"wq_down", "wkv_down", "wk_up", "router", "router_bias"},
    "moe_softmax": {"router", "w_up"},
}


def _leaves(tree):
    """{path string: (leaf name, leaf)}."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[jax.tree_util.keystr(path)] = (path[-1].key, leaf)
    return out


def _served_tokens(params, cfg, swap=None):
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i, tokens=rng.randint(0, cfg.vocab_size, n).astype(
        np.int32), max_new_tokens=6) for i, n in enumerate([5, 19, 30])]
    eng = Engine(params, cfg, max_batch=4, max_prompt=32, max_new=8)
    if swap is not None:
        eng.params = swap       # the caller's tree, cast in every call
    done, _ = eng.run(reqs)
    assert all(c.ok and len(c.tokens) == 6 for c in done)
    return [list(c.tokens) for c in done]


@pytest.mark.parametrize("case", sorted(CASES))
def test_serving_copy_dtypes(case):
    cfg = CASES[case]()
    params = init_lm(KEY, cfg)
    cast = _leaves(cast_linear_params(params, jnp.bfloat16,
                                      tied=cfg.tie_embeddings))
    names = set()
    for key, (name, leaf) in _leaves(params).items():
        assert leaf.dtype == jnp.float32, key
        gemm = name in QUANT_WEIGHT_KEYS or (name == "embed"
                                             and cfg.tie_embeddings)
        got = cast[key][1]
        assert got.dtype == (jnp.bfloat16 if gemm else jnp.float32), key
        assert got.shape == leaf.shape, key
        if not gemm:
            assert got is leaf, key
        names.add(name)
    assert MUST_HOLD[case] <= names
    if case == "mla_moe":   # layer-stacked expert weights, 4-D
        experts = [leaf for _, (name, leaf) in cast.items()
                   if name == "w_up" and leaf.ndim == 4]
        assert experts and all(w.dtype == jnp.bfloat16 for w in experts)


@pytest.mark.parametrize("case", ["dense", "tied", "mla_moe"])
def test_prefill_and_decode_logits_bit_identical(case):
    cfg = dataclasses.replace(CASES[case](), moe_dispatch="dropless") \
        if case == "mla_moe" else CASES[case]()
    params = init_lm(KEY, cfg)
    cast = cast_linear_params(params, jnp.bfloat16, tied=cfg.tie_embeddings)
    tokens = jax.random.randint(jax.random.fold_in(KEY, 1), (2, 12), 0,
                                cfg.vocab_size)

    @jax.jit
    def prefill_then_decode(p):
        caches = init_caches(cfg, 2, 32, jnp.bfloat16)
        pre, caches, _ = apply_lm(p, tokens, cfg, caches=caches,
                                  cache_index=0)
        nxt = jnp.argmax(pre[:, -1], -1).astype(jnp.int32)[:, None]
        dec, _, _ = apply_lm(p, nxt, cfg, caches=caches, cache_index=12,
                             decode=True)
        return pre, dec

    want, got = prefill_then_decode(params), prefill_then_decode(cast)
    for w, g in zip(want, got):
        assert np.isfinite(np.asarray(w, np.float32)).all()
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("case", ["dense", "mla_moe"])
def test_engine_serves_the_same_tokens_from_either_tree(case):
    cfg = CASES[case]()
    params = init_lm(KEY, cfg)
    assert _served_tokens(params, cfg) == _served_tokens(params, cfg,
                                                         swap=params)


def test_leaves_already_in_the_compute_dtype_are_not_copied():
    cfg = CASES["tied"]()
    params = init_lm(KEY, cfg)
    once = cast_linear_params(params, jnp.bfloat16, tied=True)
    twice = cast_linear_params(once, jnp.bfloat16, tied=True)
    assert all(a is b for a, b in zip(jax.tree.leaves(once),
                                      jax.tree.leaves(twice)))
    # a float32-compute config casts nothing
    same = cast_linear_params(params, jnp.float32, tied=True)
    assert all(a is b for a, b in zip(jax.tree.leaves(params),
                                      jax.tree.leaves(same)))
    # quantized containers pass whole: their float32 scales stay (the
    # untied head is the smoke tree's one 2-D GEMM weight)
    quant = quantize_linear_params(init_lm(KEY, CASES["dense"]()))
    out = cast_linear_params(quant, jnp.bfloat16, tied=False)
    qs = [w for w in jax.tree.leaves(
        out, is_leaf=lambda x: isinstance(x, QuantizedLinear))
        if isinstance(w, QuantizedLinear)]
    assert qs and all(w.q.dtype == jnp.int8 and w.scale.dtype == jnp.float32
                      for w in qs)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_params_cast_bytes(dtype):
    cfg = dataclasses.replace(get_smoke_config("internlm2-1.8b"), dtype=dtype)
    params = init_lm(KEY, cfg)
    want = sum(leaf.size * 2 for name, leaf in _leaves(params).values()
               if name in QUANT_WEIGHT_KEYS) if dtype == "bfloat16" else 0
    eng = Engine(params, cfg, max_batch=2, max_prompt=16, max_new=4)
    assert eng.params_cast_bytes == want
    if dtype == "float32":
        assert all(a is b for a, b in zip(jax.tree.leaves(params),
                                          jax.tree.leaves(eng.params)))
    else:
        assert want > 0
    obs.reset()
    obs.enable(annotate_device=False)
    try:
        eng.run([Request(rid=0, tokens=np.arange(1, 6, dtype=np.int32),
                         max_new_tokens=2)])
        snap = obs.get_metrics().snapshot()["counters"]
    finally:
        obs.disable()
        obs.reset()
    assert snap.get("engine.params_cast_bytes", 0) == want
