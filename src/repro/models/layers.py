"""Shared building blocks: initializers, norms, MLPs, RoPE, embeddings.

All modules are pure functions over explicit param dicts.  Params are created
in float32 and cast by the runtime's param-dtype policy (launch/train.py);
norm/statistics math always runs in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def dense_init(key, fan_in: int, fan_out: int, *, scale: float = 1.0,
               dtype=jnp.float32):
    std = scale / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, (fan_in, fan_out), dtype=jnp.float32)
            * std).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    return (jax.random.normal(key, (vocab, d), dtype=jnp.float32)).astype(dtype)


# ---------------------------------------------------------------- norms

def norm_init(d: int, kind: str):
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((d,), jnp.float32)}       # (1 + scale) form
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def norm_apply(params, x, kind: str, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * (1.0 + params["scale"])
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return y.astype(x.dtype)


def rms_head_norm(scale, x, eps: float = 1e-6):
    """Per-head qk-norm over the last (head_dim) axis."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * (1.0 + scale)).astype(x.dtype)


# ---------------------------------------------------------------- mlp

def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[name]


def mlp_init(key, d: int, d_ff: int, *, gated: bool = True):
    ks = jax.random.split(key, 3)
    p = {"up": dense_init(ks[0], d, d_ff),
         "down": dense_init(ks[2], d_ff, d)}
    if gated:
        p["gate"] = dense_init(ks[1], d, d_ff)
    return p


def mlp_apply(params, x, act: str):
    h = x @ params["up"].astype(x.dtype)
    if "gate" in params:
        h = act_fn(act)(x @ params["gate"].astype(x.dtype)) * h
    else:
        h = act_fn(act)(h)
    return h @ params["down"].astype(x.dtype)


# ---------------------------------------------------------------- rope

def rope_inv_freq(dim: int, theta: float, yarn=None) -> np.ndarray:
    """(dim/2,) inverse frequencies.  With a ``YaRNConfig`` they are
    DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``: pair i blends
    ``1/theta^(2i/dim)`` (weight w_i) with the same over ``factor``
    (1 - w_i), w_i = 1 - clip((i - lo) / (hi - lo), 0, 1), where lo / hi are
    the pairs that turn ``beta_fast`` / ``beta_slow`` times over
    ``original_max_position`` (floored / ceiled)."""
    if yarn is None:
        return 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    inv = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))

    def corr_dim(rot):
        return (dim * math.log(yarn.original_max_position
                               / (rot * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    hi = min(math.ceil(corr_dim(yarn.beta_slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    w = 1.0 - ramp
    return (inv / yarn.factor * (1.0 - w) + inv * w).astype(np.float32)


def rope_tables(positions, dim: int, theta: float, yarn=None):
    """positions (...,) int -> cos/sin (..., dim/2) float32 (times YaRN's
    ``mscale_ratio`` where one is given)."""
    inv = rope_inv_freq(dim, theta, yarn)
    ang = positions.astype(jnp.float32)[..., None] * inv
    if yarn is None:
        return jnp.cos(ang), jnp.sin(ang)
    m = yarn.mscale_ratio()
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def apply_rope(x, cos, sin):
    """x (..., S, dim); cos/sin broadcastable (..., S, dim/2). Paired halves."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    o1 = xf1 * cos - xf2 * sin
    o2 = xf2 * cos + xf1 * sin
    return jnp.concatenate([o1, o2], axis=-1).astype(x.dtype)


def softcap(x, cap: float):
    if not cap:
        return x
    return jnp.tanh(x / cap) * cap
