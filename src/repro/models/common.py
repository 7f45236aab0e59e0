"""Shared model primitives: norms, RoPE, parameter initialization.

Parameters are plain nested dicts of jnp arrays.  Sharding is attached by
name-based rules (see distributed/sharding.py), so leaf names here are part
of the sharding contract — do not rename casually.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, in_axis: int = 0):
    """Truncated-normal fan-in init (maps onto HF defaults closely enough)."""
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


def zeros(shape, dtype):
    return jnp.zeros(shape, dtype)


def ones(shape, dtype):
    return jnp.ones(shape, dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps: float = 1e-6, offset: bool = False):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    w = scale.astype(jnp.float32)
    y = y * (1.0 + w) if offset else y * w
    return y.astype(dt)


def layernorm(x, scale, bias=None, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dt)


def init_norm(kind: str, d: int, dtype) -> Params:
    if kind == "layernorm":
        return {"scale": ones((d,), dtype), "bias": zeros((d,), dtype)}
    return {"scale": ones((d,), dtype)}   # rmsnorm, layernorm_nobias


def apply_norm(kind: str, p: Params, x, eps: float, gemma_offset: bool = False):
    if kind == "rmsnorm":
        return rmsnorm(x, p["scale"], eps, offset=gemma_offset)
    return layernorm(x, p["scale"], p.get("bias"), eps)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float, partial: float = 1.0):
    """Inverse frequencies for the rotary fraction of the head dim."""
    rot = int(head_dim * partial)
    rot -= rot % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return inv, rot


def apply_rope(x, positions, theta: float, partial: float = 1.0):
    """x: (..., S, H, D); positions: (..., S) int32."""
    d = x.shape[-1]
    inv, rot = rope_freqs(d, theta, partial)
    if rot == 0:
        return x
    ang = positions.astype(jnp.float32)[..., None] * inv  # (..., S, rot/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    # HF "rotate_half" convention
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.concatenate([o1.astype(x.dtype), o2.astype(x.dtype), xp], axis=-1)
    return out


def sinusoidal_positions(n: int, d: int, dtype=jnp.float32):
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10_000.0, dim / d)
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    return pe[:, :d].astype(dtype)


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------

def softcap(x, cap: float):
    if cap and cap > 0:
        return jnp.tanh(x / cap) * cap
    return x


def take_embedding(table, ids):
    return jnp.take(table, ids, axis=0)
