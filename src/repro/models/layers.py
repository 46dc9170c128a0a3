"""Basic layers: norms, embeddings, rotary, initializers.

Pure JAX (no flax): every layer is an `init(key, ...) -> params` function plus
an `apply(params, x, ...) -> y` function over dict pytrees.  Parameters are
made in float32 (the training master copy); compute runs in the config
dtype.  The serving engine keeps a copy whose GEMM weights are already in
the compute dtype (`linear.cast_linear_params`).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def compute_dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


class LayerOf(NamedTuple):
    """Layer `index` of an array stacked with layers first, handed over
    whole: a Pallas kernel then reads the layer in place, where a slice of
    the stack taken inside the layer scan would be copied out first (the
    GQA k/v block pool, the MLA latent pool and the held experts' weights,
    every layer of every step)."""
    stack: jax.Array
    index: jax.Array


# --- initializers -------------------------------------------------------------------

def dense_init(key, fan_in: int, fan_out: int, scale: float = 1.0):
    std = scale / np.sqrt(fan_in)
    return jax.random.normal(key, (fan_in, fan_out), jnp.float32) * std


def embed_init(key, vocab: int, dim: int):
    return jax.random.normal(key, (vocab, dim), jnp.float32) * 0.02


# --- norms --------------------------------------------------------------------------

def norm_init(dim: int, kind: str = "rmsnorm"):
    p = {"scale": jnp.ones((dim,), jnp.float32)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((dim,), jnp.float32)
    return p


def norm_apply(p, x, kind: str = "rmsnorm", eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return y.astype(x.dtype)


# --- rotary embeddings (§VI-C2: recommended best practice) ---------------------------

def rotary_freqs(head_dim: int, theta: float = 10000.0):
    half = head_dim // 2
    inv = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    return jnp.asarray(inv)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention-magnitude correction, 0.1 mscale ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, orig_len: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN inverse frequencies (arXiv:2309.00071, as deepseek-v3 uses
    them): dimensions that turn more than `beta_fast` times over the
    original context keep theta^(-2i/dim), those that turn fewer than
    `beta_slow` times are divided by `factor`, with a linear ramp between."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr(rot):
        return dim * math.log(orig_len / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(corr(beta_fast)), 0)
    hi = min(math.ceil(corr(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    keep = 1.0 - ramp               # 1: extrapolate, 0: interpolate
    return (extra / factor * (1 - keep) + extra * keep).astype(np.float32)


def rope_inv_freq(cfg, dim: int) -> np.ndarray:
    """The model's rotary inverse frequencies for `dim` rotated dims."""
    if cfg.rope_yarn_factor:
        return yarn_inv_freq(dim, cfg.rope_theta, cfg.rope_yarn_factor,
                             cfg.rope_yarn_orig_len, cfg.rope_yarn_beta_fast,
                             cfg.rope_yarn_beta_slow)
    return np.asarray(rotary_freqs(dim, cfg.rope_theta))


def rope_magnitude(cfg) -> float:
    """Factor on cos and sin: YaRN's mscale over mscale_all_dim (1 when
    they are equal, as in deepseek-v3, or without YaRN)."""
    f = cfg.rope_yarn_factor
    return yarn_mscale(f, cfg.rope_yarn_mscale) / \
        yarn_mscale(f, cfg.rope_yarn_mscale_all_dim) if f else 1.0


def yarn_softmax_scale(cfg) -> float:
    """Factor on the attention scores: YaRN's mscale(factor,
    mscale_all_dim) squared, (0.1 ln 40 + 1)^2 ~ 1.874 for deepseek-v3."""
    f = cfg.rope_yarn_factor
    return yarn_mscale(f, cfg.rope_yarn_mscale_all_dim) ** 2 if f else 1.0


def apply_rotary(x, positions, theta: float = 10000.0, *, inv_freq=None,
                 magnitude: float = 1.0):
    """x: (..., seq, heads, head_dim); positions: (..., seq).  Rotates the
    two halves of the head dim as pairs.  inv_freq: (head_dim/2,)
    frequencies in place of theta's (YaRN: `rope_inv_freq`); magnitude
    multiplies cos and sin."""
    hd = x.shape[-1]
    inv = rotary_freqs(hd, theta) if inv_freq is None \
        else jnp.asarray(inv_freq)  # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., s, hd/2)
    sin, cos = jnp.sin(ang) * magnitude, jnp.cos(ang) * magnitude
    sin = sin[..., :, None, :]  # broadcast over heads
    cos = cos[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --- activations ---------------------------------------------------------------------

_ACTIVATIONS = {
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),  # nemotron-4 squared ReLU
}


def activation(name: str):
    try:
        return _ACTIVATIONS[name]
    except KeyError:
        raise ValueError(
            f"unknown activation {name!r}; valid: {sorted(_ACTIVATIONS)}"
        ) from None
