"""Feed-forward variants: SwiGLU / GeGLU (tanh or exact-erf GELU) / GELU-MLP."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import GEGLU, GEGLU_ERF, GELU_MLP, SWIGLU, ModelConfig
from repro.models import common as cm


def init_ffn(key, cfg: ModelConfig, kind: str):
    dt = cm.dtype_of(cfg.dtype)
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if kind in (SWIGLU, GEGLU, GEGLU_ERF):
        return {"w_gate": cm.dense_init(ks[0], (d, f), dt),
                "w_up": cm.dense_init(ks[1], (d, f), dt),
                "w_down": cm.dense_init(ks[2], (f, d), dt)}
    if kind == GELU_MLP:
        return {"w_in": cm.dense_init(ks[0], (d, f), dt),
                "w_out": cm.dense_init(ks[1], (f, d), dt)}
    raise ValueError(kind)


def apply_ffn(p, kind: str, x):
    if kind == SWIGLU:
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
        return h @ p["w_down"]
    if kind == GEGLU:
        h = jax.nn.gelu(x @ p["w_gate"], approximate=True) * (x @ p["w_up"])
        return h @ p["w_down"]
    if kind == GEGLU_ERF:
        h = jax.nn.gelu(x @ p["w_gate"], approximate=False) * (x @ p["w_up"])
        return h @ p["w_down"]
    if kind == GELU_MLP:
        return jax.nn.gelu(x @ p["w_in"], approximate=True) @ p["w_out"]
    raise ValueError(kind)
