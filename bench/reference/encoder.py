"""Plain reference of the ModernBERT encoder (gte-modernbert-base), in
float32.

Written from the published description (arXiv:2412.13663; the Hugging
Face config's keys name the numbers): ``x = LN(E[tok])``; layer i adds
``Wo Attn(LN_a(x))`` (``LN_a`` the identity for layer 0), bidirectional
over ``[CLS] ... [SEP]``, RoPE on the whole head, global when ``i %
global_attn_every_n_layers == 0`` (theta ``global_rope_theta``), else
local, keys ``|i - j| <= local_attention // 2`` (theta
``local_rope_theta``); then adds ``W_down (GELU_erf(y W_gate) * y
W_up)`` of ``y = LN_m(x)``; a final LN of the CLS row, L2-normalized.
LayerNorms have no bias (and start at scale one).  Every product runs
in float32 at ``HIGHEST`` precision.

Each sequence is computed alone: its keys are masked past its length
and, in a local layer, outside the band, by an explicit (query, key)
mask, and its queries go in blocks of ``QB`` so 8,192 tokens fit (a
local layer's block reads only the keys that can be in its band).
Sequences are padded to a power of two and vmapped, which changes no
number, so that the program compiles a few times.

The tokenizer is the program's documented stand-in, written again:
whitespace words, each the CRC-32 of its UTF-8 bytes started from the
seed, modulo the vocabulary less three; [CLS], [SEP], [PAD] are the top
three ids.

The weights are not taken from the program.  They are drawn again from
the seed by the documented recipe: ``PRNGKey(seed)`` split in two (the
token embedding, a normal times 0.02; the stack); the stack key split in
three (prefix, unit, suffix keys); layer 0 from ``fold_in(prefix, 0)``,
layer ``1 + g r + j`` (g the global period) from
``split(fold_in(unit, j), n_units)[r]``, the rest from ``fold_in(suffix,
i)``.  A layer key splits in six: attention from the first (split in
eight: wq, wk, wv, wo), the feed-forward from the third (split in
three: gate, up, down).  Dense weights are truncated normals on [-2, 2]
over the square root of their first axis; each is cast to the served
dtype and read back in float32.

``quant="fp8"`` is the lower-precision control: every product with a
weight takes float8 (e4m3) weights, scaled per output channel, and
float8 activations, scaled per token, as an fp8 serving path would.
"""
from __future__ import annotations

import functools
import math
import zlib
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
QB = 512
PADS = tuple(2 ** k for k in range(5, 14))   # 32 ... 8,192: padded lengths
BATCH_TOKENS = 8192            # padded tokens vmapped in one call
_DT = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
ARCH = ("hidden_size", "num_hidden_layers", "num_attention_heads",
        "intermediate_size", "vocab_size", "global_attn_every_n_layers",
        "local_attention", "global_rope_theta", "local_rope_theta",
        "norm_eps", "dtype")


def tokenize(text: str, vocab_size: int, max_len: int, seed: int
             ) -> List[int]:
    cls = vocab_size - 3
    ids = [zlib.crc32(w.encode("utf-8"), seed) % cls
           for w in text.split()[: max_len - 2]]
    return [cls] + ids + [vocab_size - 2]


def _dense(key, shape, dtype):
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) \
        / math.sqrt(shape[0])
    return w.astype(dtype).astype(jnp.float32)


def _layer_keys(k_stack, n_layers: int, g: int) -> List:
    kp, ku, ks = jax.random.split(k_stack, 3)
    n_units = (n_layers - 1) // g
    units = [jax.random.split(jax.random.fold_in(ku, j), n_units)
             for j in range(g)] if n_units else []
    keys = [jax.random.fold_in(kp, 0)]
    keys += [units[j][r] for r in range(n_units) for j in range(g)]
    return keys + [jax.random.fold_in(ks, i)
                   for i in range(n_layers - len(keys))]


def weights(shapes: Dict, seed: int) -> Dict:
    """Every weight in float32, the layers stacked on a leading axis."""
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
    return {"emb": emb,
            "layers": jax.tree.map(lambda *x: jnp.stack(x), *layers)}


def _f8(x, axis):
    """float8 (e4m3) fake-quantization, one scale per ``axis`` group."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant, n_in):
    """x (..., K) times w (its first ``n_in`` axes contracted)."""
    if quant == "fp8":
        x = _f8(x, tuple(range(x.ndim - n_in, x.ndim)))
        w = _f8(w, tuple(range(n_in)))
    return jnp.tensordot(x, w, axes=n_in, precision=HI)


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


def _one(w, tokens, n, arch, quant):
    """One sequence: ``tokens`` (P,), real length ``n`` -> (d,)."""
    s = dict(arch)
    p = tokens.shape[0]
    hd = s["hidden_size"] // s["num_attention_heads"]
    half = s["local_attention"] // 2
    eps = s["norm_eps"]
    qb = min(QB, p)
    key = jnp.arange(p)
    x = _ln(w["emb"][tokens], eps)

    def layer(x, lw):
        i = lw["i"]
        glob = i % s["global_attn_every_n_layers"] == 0
        theta = jnp.where(glob, s["global_rope_theta"],
                          s["local_rope_theta"])
        y = jnp.where(i == 0, x, _ln(x, eps))
        q = _rope(_mm(y, lw["wq"], quant, 1), theta)
        k = _rope(_mm(y, lw["wk"], quant, 1), theta)
        v = _mm(y, lw["wv"], quant, 1)

        def attend(qs, qi, kk, vv, kj):
            mask = (kj[None] >= 0) & (kj[None] < n) & (glob | (
                jnp.abs(qi[:, None] - kj[None]) <= half))
            sc = jnp.einsum("qhd,khd->hqk", qs, kk,
                            precision=HI) / math.sqrt(hd)
            # finite, so the padding's rows stay finite too
            pr = jax.nn.softmax(jnp.where(mask[None], sc, -1e30), -1)
            return jnp.einsum("hqk,khd->qhd", pr, vv, precision=HI)

        near = qb + 2 * half                   # keys a local block can see
        kp = jnp.pad(k, ((half, near), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((half, near), (0, 0), (0, 0)))

        def block(start):
            qs = jax.lax.dynamic_slice_in_dim(q, start, qb)
            qi = start + jnp.arange(qb)
            local = lambda: attend(   # noqa: E731
                qs, qi, jax.lax.dynamic_slice_in_dim(kp, start, near),
                jax.lax.dynamic_slice_in_dim(vp, start, near),
                start - half + jnp.arange(near))
            return jax.lax.cond(glob, lambda: attend(qs, qi, k, v, key),
                                local)

        o = jax.lax.map(block, jnp.arange(0, p, qb)).reshape(q.shape)
        x = x + _mm(o, lw["wo"], quant, 2)
        y = _ln(x, eps)
        u = jax.nn.gelu(_mm(y, lw["w_gate"], quant, 1), approximate=False)
        x = x + _mm(u * _mm(y, lw["w_up"], quant, 1), lw["w_down"],
                    quant, 1)
        return x, None

    layers = dict(w["layers"], i=jnp.arange(s["num_hidden_layers"]))
    x, _ = jax.lax.scan(layer, x, layers)
    cls = _ln(x[0], eps)
    return cls / jnp.linalg.norm(cls)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _batch(w, tokens, n, arch, quant):
    return jax.vmap(lambda t, m: _one(w, t, m, arch, quant))(tokens, n)


def embed(shapes: Dict, seed: int, seqs: Sequence[Sequence[int]],
          quant: str = "f32", w: Dict = None) -> np.ndarray:
    """(N, d) float64 unit embeddings of token sequences (at most the
    longest of ``PADS``)."""
    arch = tuple((k, shapes[k]) for k in ARCH)
    w = w if w is not None else weights(shapes, seed)
    out = np.zeros((len(seqs), shapes["hidden_size"]), np.float64)
    by: Dict[int, List[int]] = {}
    for i, t in enumerate(seqs):
        by.setdefault(next(p for p in PADS if p >= len(t)), []).append(i)
    with jax.default_matmul_precision("highest"):
        for p, idx in sorted(by.items()):
            rows = max(1, BATCH_TOKENS // p)
            for j in range(0, len(idx), rows):
                part = idx[j: j + rows]
                tok = np.zeros((rows, p), np.int32)
                n = np.ones(rows, np.int32)
                for r, i in enumerate(part):
                    tok[r, : len(seqs[i])] = seqs[i]
                    n[r] = len(seqs[i])
                got = _batch(w, jnp.asarray(tok), jnp.asarray(n), arch, quant)
                out[part] = np.asarray(got, np.float64)[: len(part)]
    return out
