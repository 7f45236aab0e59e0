"""Plain reference of the served dense decoders, in float32.

One pre-norm decoder block, written from the published descriptions of
InternLM2 (RMSNorm, GQA, RoPE theta 1e6, SwiGLU) and StableLM-2
(LayerNorm, full MHA, rotary on the first quarter of each head, SwiGLU):
norm, Q/K/V projections, rotary positions (HF rotate-half convention),
causal softmax attention with grouped K/V heads, output projection,
residual, norm, SwiGLU feed-forward, residual; a final norm and an
untied LM head.  Every product runs in float32 at ``HIGHEST`` precision.

The weights are not taken from the program.  They are drawn again from
the backend's seed by the documented recipe: ``PRNGKey(seed)`` split in
five (embedding, stack, head, ...); the stack key split in three, the
middle one folded with 0 and split once per layer; each layer key split
in six, attention from the first (split in eight: wq, wk, wv, wo) and
the feed-forward from the third (split in three: gate, up, down).  Dense
weights are truncated normals on [-2, 2] scaled by 1/sqrt(fan-in) (the
first axis, heads for wo); the embedding and LM head are normals times
0.02; norms start at one (and zero bias).  Each is cast to the served
dtype and read back in float32.  Layers are made and applied one at a
time, so the reference never holds more than one layer's weights.

``quant="fp8"`` is the lower-precision control: every matrix product
takes float8 (e4m3) weights, scaled per output channel, and float8
activations, scaled per token, as an fp8 serving path would.  (An int8
control of the same layout read within two times of the bf16 program
on every number, so it cannot separate a sound run from a degraded one.)
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
_DT = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _dense(key, shape, fan_in, dtype):
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) \
        * (1.0 / math.sqrt(fan_in))
    return w.astype(dtype).astype(jnp.float32)


def _embed(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02
            ).astype(dtype).astype(jnp.float32)


def _keys(seed: int, n_layers: int):
    k_emb, k_stack, k_head, _, _ = jax.random.split(jax.random.PRNGKey(seed),
                                                    5)
    _, ku, _ = jax.random.split(k_stack, 3)
    return k_emb, k_head, jax.random.split(jax.random.fold_in(ku, 0),
                                           n_layers)


@functools.partial(jax.jit, static_argnums=(1,))
def _layer_weights(key, arch: Tuple) -> Dict[str, jnp.ndarray]:
    s = dict(arch)
    d, h, kv, hd, f = (s["d_model"], s["n_heads"], s["n_kv_heads"],
                       s["head_dim"], s["d_ff"])
    dt = _DT[s["dtype"]]
    ks = jax.random.split(key, 6)
    a = jax.random.split(ks[0], 8)
    fk = jax.random.split(ks[2], 3)
    return {"wq": _dense(a[0], (d, h, hd), d, dt),
            "wk": _dense(a[1], (d, kv, hd), d, dt),
            "wv": _dense(a[2], (d, kv, hd), d, dt),
            "wo": _dense(a[3], (h, hd, d), h, dt),
            "w_gate": _dense(fk[0], (d, f), d, dt),
            "w_up": _dense(fk[1], (d, f), d, dt),
            "w_down": _dense(fk[2], (f, d), f, dt)}


def _norm(x, s):
    if s["norm"] == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + s["norm_eps"])
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + s["norm_eps"])


def _f8(x, axis):
    """float8 (e4m3) fake-quantization, one scale per ``axis`` group."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant, w_in_axes):
    """x (..., K) times w (K-axes first) in float32, optionally fp8."""
    if quant == "fp8":
        x = _f8(x, -1)
        w = _f8(w, w_in_axes)
    return jnp.tensordot(x, w, axes=len(w_in_axes), precision=HI)


def _rope(x, pos, theta, partial):
    hd = x.shape[-1]
    rot = int(hd * partial)
    rot -= rot % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos.astype(jnp.float32)[:, None] * inv[None]      # (S, rot/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _block(x, w, arch: Tuple, quant: str):
    """One decoder block over one sequence x (S, d)."""
    s = dict(arch)
    h, kv, hd = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    n = x.shape[0]
    pos = jnp.arange(n)
    y = _norm(x, s)
    q = _mm(y, w["wq"], quant, (0,))                       # (S, H, hd)
    k = _mm(y, w["wk"], quant, (0,))
    v = _mm(y, w["wv"], quant, (0,))
    q = _rope(q, pos, s["rope_theta"], s["partial_rotary"])
    k = _rope(k, pos, s["rope_theta"], s["partial_rotary"])
    g = h // kv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    sc = jnp.einsum("shd,thd->hst", q, k, precision=HI) / math.sqrt(hd)
    sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("hst,thd->shd", p, v, precision=HI)
    x = x + _mm(o, w["wo"], quant, (0, 1))
    y = _norm(x, s)
    f = jax.nn.silu(_mm(y, w["w_gate"], quant, (0,))) \
        * _mm(y, w["w_up"], quant, (0,))
    return x + _mm(f, w["w_down"], quant, (0,))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _gaps(x, head, served, arch: Tuple, quant: str):
    """Final norm and LM head at the served positions of one sequence:
    -> (gap of the served token, gap of the token this precision puts
    first), both against the best logit of this precision's head."""
    s = dict(arch)
    logits = _mm(_norm(x, s), head, quant, (0,))           # (P, V)
    best = logits.max(axis=-1)
    got = jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0]
    return best - got, jnp.argmax(logits, axis=-1)


def arch_key(shapes: Dict) -> Tuple:
    keys = ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
            "vocab_size", "norm", "norm_eps", "rope_theta",
            "partial_rotary", "dtype", "n_layers")
    return tuple((k, shapes[k]) for k in keys)


def forward_hidden(shapes: Dict, seed: int, seqs: Sequence[np.ndarray],
                   quant: str = "f32") -> List[jnp.ndarray]:
    """Final hidden states (before the last norm) of each token sequence,
    layer by layer; sequences are right-padded to a shared length so
    the block compiles once (causality keeps padding out of earlier
    positions)."""
    arch = arch_key(shapes)
    dt = _DT[shapes["dtype"]]
    k_emb, _, layer_keys = _keys(seed, shapes["n_layers"])
    emb = _embed(k_emb, (shapes["vocab_size"], shapes["d_model"]), dt)
    width = 128 * -(-max(len(t) for t in seqs) // 128)
    xs = []
    for t in seqs:
        ids = np.zeros(width, np.int32)
        ids[: len(t)] = t
        xs.append(emb[jnp.asarray(ids)])
    del emb
    for lk in layer_keys:
        w = _layer_weights(lk, arch)
        xs = [_block(x, w, arch, quant) for x in xs]
    return xs


def head_weights(shapes: Dict, seed: int) -> jnp.ndarray:
    dt = _DT[shapes["dtype"]]
    _, k_head, _ = _keys(seed, shapes["n_layers"])
    return _embed(k_head, (shapes["vocab_size"], shapes["d_model"]), dt).T


def served_gaps(shapes: Dict, seed: int, samples: Sequence[Dict],
                quant: str = "f32") -> Dict[str, np.ndarray]:
    """For each sample {"tokens": full sequence, "first": index of the
    position that predicts the first served token, "served": tokens}:
    the reference's gap below its best logit of each served token, and
    the token this precision's argmax puts first at each position."""
    seqs = [np.asarray(s["tokens"], np.int32) for s in samples]
    hidden = forward_hidden(shapes, seed, seqs, quant)
    head = head_weights(shapes, seed)
    gaps, firsts = [], []
    for s, x in zip(samples, hidden):
        n = len(s["served"])
        at = x[s["first"]: s["first"] + n]
        g, top = _gaps(at, head, jnp.asarray(s["served"], jnp.int32),
                       arch_key(shapes), quant)
        gaps.append(np.asarray(g))
        firsts.append(np.asarray(top))
    return {"gap": np.concatenate(gaps), "top": np.concatenate(firsts)}
