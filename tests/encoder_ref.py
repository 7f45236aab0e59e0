"""Plain reference of the ModernBERT encoder for the tests, in float32.

Written from the published description (arXiv:2412.13663): ``x =
LN(E[tok])``; layer i adds ``Wo Attn(LN_a(x))`` (``LN_a`` the identity
for layer 0), bidirectional, RoPE on the whole head, global when ``i %
global_every == 0`` (theta ``global_theta``) and otherwise local, keys
``|i - j| <= local // 2`` (theta ``local_theta``); then adds
``W_down (GELU_erf(x W_gate) * x W_up)`` of ``LN_m(x)``; a final LN,
the CLS row, L2-normalized.  LayerNorms have no bias.

One sequence at a time, no padding, an explicit (query, key) mask, query
rows in blocks of ``QB``; every product at ``HIGHEST`` precision.
Weights are drawn again from the seed by the recipe the program
documents (``repro.models.encoder``), not taken from the program.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
QB = 512
_DT = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _dense(key, shape, dtype):
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) \
        / math.sqrt(shape[0])
    return w.astype(dtype).astype(jnp.float32)


def _layer_keys(k_stack, n_layers: int, g: int) -> List:
    """Layer 0 from the prefix key; layers 1 + g r + j from the unit key
    of slot j, repeat r (g the global period); the rest from the suffix
    key."""
    kp, ku, ks = jax.random.split(k_stack, 3)
    n_units = (n_layers - 1) // g
    units = [jax.random.split(jax.random.fold_in(ku, j), n_units)
             for j in range(g)] if n_units else []
    keys = [jax.random.fold_in(kp, 0)]
    keys += [units[j][r] for r in range(n_units) for j in range(g)]
    keys += [jax.random.fold_in(ks, i)
             for i in range(n_layers - len(keys))]
    return keys


def weights(shapes: Dict, seed: int) -> Dict:
    d, h, f = (shapes["hidden_size"], shapes["num_attention_heads"],
               shapes["intermediate_size"])
    hd = d // h
    dt = _DT[shapes["dtype"]]
    k_emb, k_stack = jax.random.split(jax.random.PRNGKey(seed))
    emb = (jax.random.normal(k_emb, (shapes["vocab_size"], d), jnp.float32)
           * 0.02).astype(dt).astype(jnp.float32)
    layers = []
    for key in _layer_keys(k_stack, shapes["num_hidden_layers"],
                           shapes["global_attn_every_n_layers"]):
        ks = jax.random.split(key, 6)
        a = jax.random.split(ks[0], 8)
        fk = jax.random.split(ks[2], 3)
        layers.append({"wq": _dense(a[0], (d, h, hd), dt),
                       "wk": _dense(a[1], (d, h, hd), dt),
                       "wv": _dense(a[2], (d, h, hd), dt),
                       "wo": _dense(a[3], (h, hd, d), dt),
                       "w_gate": _dense(fk[0], (d, f), dt),
                       "w_up": _dense(fk[1], (d, f), dt),
                       "w_down": _dense(fk[2], (f, d), dt)})
    return {"emb": emb, "layers": layers}


def _ln(x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, theta):
    n, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, half):
    """q, k, v (n, H, hd); ``half`` None for a global layer."""
    n, _, hd = q.shape
    outs = []
    for s in range(0, n, QB):
        qi = jnp.arange(s, min(n, s + QB))
        sc = jnp.einsum("qhd,khd->hqk", q[s: s + QB], k,
                        precision=HI) / math.sqrt(hd)
        if half is not None:
            near = jnp.abs(qi[:, None] - jnp.arange(n)[None]) <= half
            sc = jnp.where(near[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HI))
    return jnp.concatenate(outs, 0)


def embed_one(shapes: Dict, w: Dict, tokens: Sequence[int]) -> np.ndarray:
    eps = shapes["norm_eps"]
    x = _ln(w["emb"][jnp.asarray(tokens, jnp.int32)], eps)
    for i, lw in enumerate(w["layers"]):
        glob = i % shapes["global_attn_every_n_layers"] == 0
        theta = shapes["global_rope_theta" if glob else "local_rope_theta"]
        y = x if i == 0 else _ln(x, eps)
        q, k, v = (_rope(jnp.einsum("nd,dhk->nhk", y, lw[m], precision=HI),
                         theta) if m != "wv" else
                   jnp.einsum("nd,dhk->nhk", y, lw[m], precision=HI)
                   for m in ("wq", "wk", "wv"))
        o = _attend(q, k, v, None if glob else shapes["local_attention"] // 2)
        x = x + jnp.einsum("nhk,hkd->nd", o, lw["wo"], precision=HI)
        y = _ln(x, eps)
        u = jnp.dot(y, lw["w_gate"], precision=HI)
        g = jnp.dot(y, lw["w_up"], precision=HI)
        x = x + jnp.dot(jax.nn.gelu(u, approximate=False) * g,
                        lw["w_down"], precision=HI)
    cls = _ln(x[0], eps)
    return np.asarray(cls / jnp.linalg.norm(cls), np.float64)
