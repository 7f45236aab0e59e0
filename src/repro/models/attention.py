"""Self/cross attention: MHA, GQA, MQA; sliding windows; three prefill
implementations (full, chunked online-softmax, banded windowed); ring-buffer
decode caches; bidirectional (encoder) attention over right-padded rows,
global as the chunked online softmax and windowed as a symmetric band.

Layout conventions:
  activations  x: (B, S, D)
  q            (B, S, H, hd)
  k, v         (B, S, KV, hd)
  cache k/v    (B, W, KV, hd)   W = min(max_seq, window or max_seq)
Keys are stored *post-RoPE* in the cache, so decode needs no re-rotation.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import common as cm

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, spec: LayerSpec, *, cross: bool = False):
    dt = cm.dtype_of(cfg.dtype)
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 8)
    pfx = "cross_" if cross else ""
    p = {
        pfx + "wq": cm.dense_init(ks[0], (d, h, hd), dt),
        pfx + "wk": cm.dense_init(ks[1], (d, kv, hd), dt),
        pfx + "wv": cm.dense_init(ks[2], (d, kv, hd), dt),
        pfx + "wo": cm.dense_init(ks[3], (h, hd, d), dt, in_axis=0),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = cm.ones((hd,), dt)
        p["k_norm"] = cm.ones((hd,), dt)
    return p


# ---------------------------------------------------------------------------
# Core score/combine helpers (grouped-query layout)
# ---------------------------------------------------------------------------

def _group(q, kv_heads):
    b, s, h, hd = q.shape
    return q.reshape(b, s, kv_heads, h // kv_heads, hd)


def _scores(qg, k, scale):
    # qg: (B,S,KV,G,hd)  k: (B,T,KV,hd) -> (B,KV,G,S,T), f32
    return jnp.einsum("bskgh,btkh->bkgst", qg, k,
                      preferred_element_type=jnp.float32) * scale


def _combine(probs, v, dtype):
    # probs: (B,KV,G,S,T)  v: (B,T,KV,hd) -> (B,S,KV*G,hd)
    out = jnp.einsum("bkgst,btkh->bskgh", probs.astype(v.dtype), v)
    b, s, kv, g, hd = out.shape
    return out.reshape(b, s, kv * g, hd).astype(dtype)


def _causal_mask(q_pos, k_pos, window: Optional[int]):
    m = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= k_pos[None, :] > (q_pos[:, None] - window)
    return m


# ---------------------------------------------------------------------------
# Prefill / train paths
# ---------------------------------------------------------------------------

def attend_full(q, k, v, q_pos, k_pos, *, causal: bool, window, scale, softcap=0.0):
    qg = _group(q, k.shape[2])
    s = _scores(qg, k, scale)
    s = cm.softcap(s, softcap)
    if causal:
        mask = _causal_mask(q_pos, k_pos, window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return _combine(p, v, q.dtype)


def attend_chunked(q, k, v, q_pos, k_pos, *, causal: bool, window, scale,
                   chunk: int, softcap=0.0, kv_len=None):
    """Online-softmax scan over KV chunks (flash-style, O(S*chunk) live).

    Non-causal: every query sees the keys at positions below its row's
    ``kv_len`` (B,) (default: all of them).  A chunk whose keys are all
    masked leaves the running state bit for bit as it was, so a row's
    result does not depend on how far it is padded."""
    b, s, h, hd = q.shape
    t = k.shape[1]
    if not causal and kv_len is None:
        kv_len = jnp.full((b,), t, jnp.int32)
    nc = max(1, -(-t // chunk))
    pad = nc * chunk - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=2**30)
    kv_heads = k.shape[2]
    qg = _group(q, kv_heads)
    kc = k.reshape(b, nc, chunk, kv_heads, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nc, chunk, kv_heads, hd).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(nc, chunk)

    def step(carry, xs):
        m, l, acc = carry
        kb, vb, pb = xs
        sb = _scores(qg, kb, scale)               # (B,KV,G,S,C)
        sb = cm.softcap(sb, softcap)
        if causal:
            mask = _causal_mask(q_pos, pb, window)
            sb = jnp.where(mask[None, None, None], sb, NEG_INF)
        else:
            mask = pb[None, :] < kv_len[:, None]              # (B, C)
            sb = jnp.where(mask[:, None, None, None], sb, NEG_INF)
        m_new = jnp.maximum(m, sb.max(axis=-1))
        r = jnp.exp(m - m_new)
        p = jnp.exp(sb - m_new[..., None])
        l = l * r + p.sum(axis=-1)
        acc = acc * r[..., None] + jnp.einsum(
            "bkgsc,bckh->bkgsh", p.astype(vb.dtype), vb).astype(jnp.float32)
        return (m_new, l, acc), None

    g = h // kv_heads
    m0 = jnp.full((b, kv_heads, g, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kv_heads, g, s), jnp.float32)
    a0 = jnp.zeros((b, kv_heads, g, s, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, hd)
    return out.astype(q.dtype)


def attend_banded(q, k, v, q_pos, k_pos, *, window: int, scale, softcap=0.0):
    """Windowed causal attention in O(S*2w): query chunk i attends KV
    chunks i-1 and i (chunk size = window).  Requires S % window == 0."""
    b, s, h, hd = q.shape
    w = window
    assert s % w == 0, "banded prefill needs seq % window == 0"
    nc = s // w
    kv_heads = k.shape[2]
    qc = q.reshape(b, nc, w, h, hd)
    kc = k.reshape(b, nc, w, kv_heads, hd)
    vc = v.reshape(b, nc, w, kv_heads, hd)
    zk = jnp.zeros_like(kc[:, :1])
    kprev = jnp.concatenate([zk, kc[:, :-1]], axis=1)
    vprev = jnp.concatenate([jnp.zeros_like(vc[:, :1]), vc[:, :-1]], axis=1)
    k2 = jnp.concatenate([kprev, kc], axis=2)     # (B,nc,2w,KV,hd)
    v2 = jnp.concatenate([vprev, vc], axis=2)
    qg = qc.reshape(b, nc, w, kv_heads, h // kv_heads, hd)
    sc = jnp.einsum("bnskgh,bntkh->bnkgst", qg, k2,
                    preferred_element_type=jnp.float32) * scale
    sc = cm.softcap(sc, softcap)
    qp = q_pos.reshape(nc, w)
    kp = jnp.concatenate(
        [qp - w, qp], axis=1)                     # (nc, 2w) positions
    mask = (kp[:, None, :] <= qp[:, :, None]) & \
           (kp[:, None, :] > qp[:, :, None] - w) & (kp[:, None, :] >= 0)
    sc = jnp.where(mask[None, :, None, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bnkgst,bntkh->bnskgh", p.astype(v2.dtype), v2)
    return out.reshape(b, s, h, hd).astype(q.dtype)


def attend_band(q, k, v, kv_len=None, *, window: int, scale):
    """Bidirectional windowed attention: query i sees keys j with
    |i - j| <= window // 2 and j below its row's ``kv_len`` (B,)
    (default: all of them).  Only
    the band is computed: the sequence is cut in blocks of window // 2,
    and each query block scores the keys of its own block and the two
    beside it, so the work is O(S * 1.5 window) for any length S."""
    b, s, h, hd = q.shape
    if kv_len is None:
        kv_len = jnp.full((b,), s, jnp.int32)
    half = window // 2
    nb = -(-s // half)
    pad = nb * half - s
    kv_heads = k.shape[2]
    pads = ((0, 0), (0, pad), (0, 0), (0, 0))
    qb = jnp.pad(q, pads).reshape(b, nb, half, kv_heads, h // kv_heads, hd)

    def three(x):                      # (B, nb, 3*half, KV, hd)
        x = jnp.pad(x, ((0, 0), (half, pad + half), (0, 0), (0, 0)))
        x = x.reshape(b, nb + 2, half, kv_heads, hd)
        return jnp.concatenate([x[:, :-2], x[:, 1:-1], x[:, 2:]], axis=2)

    k3, v3 = three(k), three(v)
    sc = jnp.einsum("bnskgh,bntkh->bnkgst", qb, k3,
                    preferred_element_type=jnp.float32) * scale
    qp = jnp.arange(nb * half).reshape(nb, half)
    kp = qp[:, :1] - half + jnp.arange(3 * half)[None]      # (nb, 3*half)
    near = jnp.abs(qp[:, :, None] - kp[:, None, :]) <= half
    mask = near[None] & (kp >= 0)[None, :, None] & (
        kp[None, :, None, :] < kv_len[:, None, None, None])  # (B,nb,half,3h)
    sc = jnp.where(mask[:, :, None, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bnkgst,bntkh->bnskgh", p.astype(v3.dtype), v3)
    return out.reshape(b, nb * half, h, hd)[:, :s].astype(q.dtype)


# ---------------------------------------------------------------------------
# Public layer application
# ---------------------------------------------------------------------------

def self_attention(p, cfg: ModelConfig, spec: LayerSpec, x, positions,
                   *, cache=None, pos=None, collect: Optional[int] = None,
                   kv_len=None):
    """cache=None -> train/prefill over full x.
    cache={'k','v'} + scalar pos -> single-token decode (x: (B,1,D)).
    collect=max_seq -> prefill also returns a decode-ready KV cache.
    kv_len (B,) -> a bidirectional layer's rows are right-padded past it."""
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    theta = spec.rope_theta or cfg.rope_theta
    scale = hd ** -0.5
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = cm.rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = cm.rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if cfg.partial_rotary > 0:  # whisper sets 0.0 (sinusoidal abs pos)
        q = cm.apply_rope(q, positions, theta, cfg.partial_rotary)
        k = cm.apply_rope(k, positions, theta, cfg.partial_rotary)

    if cache is None:
        if not spec.causal and spec.window is not None:
            out = attend_band(q, k, v, kv_len, window=spec.window,
                              scale=scale)
        elif not spec.causal:
            out = attend_chunked(q, k, v, positions[0], positions[0],
                                 causal=False, window=None, scale=scale,
                                 chunk=min(cfg.attn_chunk, x.shape[1]),
                                 softcap=cfg.logit_softcap, kv_len=kv_len)
        elif (spec.window is not None and cfg.window_prefill_banded
              and x.shape[1] % spec.window == 0 and x.shape[1] > spec.window):
            out = attend_banded(q, k, v, positions[0], positions[0],
                                window=spec.window, scale=scale,
                                softcap=cfg.logit_softcap)
        elif cfg.attn_impl == "chunked" and x.shape[1] > cfg.attn_chunk:
            out = attend_chunked(q, k, v, positions[0], positions[0],
                                 causal=True, window=spec.window, scale=scale,
                                 chunk=cfg.attn_chunk, softcap=cfg.logit_softcap)
        else:
            out = attend_full(q, k, v, positions[0], positions[0],
                              causal=True, window=spec.window, scale=scale,
                              softcap=cfg.logit_softcap)
        new_cache = None
        if collect is not None:
            new_cache = _collect_cache(k, v, positions, spec, collect)
    elif x.shape[1] == 1:
        out, new_cache = _decode_attend(q, k, v, cache, pos, spec, cfg, scale)
    else:
        out, new_cache = _chunk_attend(q, k, v, cache, positions, spec,
                                       cfg, scale)

    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


def _collect_cache(k, v, positions, spec: LayerSpec, max_seq: int):
    """Build a decode-ready cache from prefill K/V (post-RoPE)."""
    b, s, kv, hd = k.shape
    if spec.window is not None and min(max_seq, spec.window) < s:
        w = min(max_seq, spec.window)
        slots = positions[0][-w:] % w
        ck = jnp.zeros((b, w, kv, hd), k.dtype).at[:, slots].set(k[:, -w:])
        cv = jnp.zeros((b, w, kv, hd), v.dtype).at[:, slots].set(v[:, -w:])
    else:
        w = min(max_seq, spec.window) if spec.window is not None else max_seq
        ck = jnp.zeros((b, w, kv, hd), k.dtype).at[:, :s].set(k[:, :w])
        cv = jnp.zeros((b, w, kv, hd), v.dtype).at[:, :s].set(v[:, :w])
    return {"k": ck, "v": cv}


def _decode_attend(q, k_new, v_new, cache, pos, spec: LayerSpec,
                   cfg: ModelConfig, scale):
    """One-token decode against a (possibly ring-buffer) cache.

    ``pos`` is either a scalar (whole batch at one position — the
    whole-batch decode loop) or a (B,) vector of per-row positions (the
    slot-based scheduler: each decode slot is at its own depth)."""
    ck, cv = cache["k"], cache["v"]
    b, w = ck.shape[0], ck.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    scalar_pos = pos.ndim == 0
    posv = jnp.broadcast_to(pos, (b,))
    slot = posv % w if spec.window is not None else jnp.minimum(posv, w - 1)
    rows = jnp.arange(b)
    ck = ck.at[rows, slot].set(k_new[:, 0].astype(ck.dtype))
    cv = cv.at[rows, slot].set(v_new[:, 0].astype(cv.dtype))
    n_valid = jnp.minimum(posv + 1, w)
    if cfg.decode_kernel and cfg.logit_softcap == 0.0 and scalar_pos:
        # flash-decoding Pallas kernel (kernels/decode_gqa.py): online-
        # softmax over KV blocks, scratch state in VMEM.  Valid-slot
        # semantics match both the ring buffer (n_valid) and the full
        # cache (pos+1) cases.  The kernel takes one scalar n_valid, so
        # vector-pos (slot scheduler) traffic uses the masked jnp path.
        from repro.kernels import ops as kops
        out = kops.decode_gqa(q[:, 0], ck, cv, jnp.minimum(pos + 1, w),
                              block_s=min(512, ck.shape[1]))
        return out[:, None], {"k": ck, "v": cv}
    if spec.window is not None:
        # ring buffer: slot i holds absolute position whose (abs % w)==i;
        # all written slots are within the window by construction.
        valid = jnp.arange(w)[None, :] < n_valid[:, None]
    else:
        valid = jnp.arange(w)[None, :] <= posv[:, None]
    qg = _group(q, ck.shape[2])
    s = _scores(qg, ck, scale)
    s = cm.softcap(s, cfg.logit_softcap)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = _combine(p, cv, q.dtype)
    return out, {"k": ck, "v": cv}


def _chunk_attend(q, k_new, v_new, cache, positions, spec: LayerSpec,
                  cfg: ModelConfig, scale):
    """Multi-token cache extension: chunked prefill's attention step.

    Writes a (B, C) chunk of K/V into the cache at per-row absolute
    ``positions`` (position i = row's chunk start + i) and attends each
    query against the full cache width with a per-query causal validity
    mask ``cache_slot <= position``.  Later writes from this same chunk
    sit at strictly greater positions, so causality falls out of the
    mask with no intra-chunk special case; cache slots past the row's
    true prompt length hold garbage that the mask excludes until decode
    overwrites them.  Windowed (ring-buffer) caches are not supported —
    a chunk could wrap the ring — which ``supports_chunked_prefill``
    gates at the model level."""
    if spec.window is not None:
        raise ValueError("chunked prefill does not support windowed "
                         "attention caches")
    ck, cv = cache["k"], cache["v"]
    b, w = ck.shape[0], ck.shape[1]
    positions = jnp.asarray(positions, jnp.int32)
    rows = jnp.arange(b)[:, None]
    ck = ck.at[rows, positions].set(k_new.astype(ck.dtype))
    cv = cv.at[rows, positions].set(v_new.astype(cv.dtype))
    valid = jnp.arange(w)[None, None, :] <= positions[:, :, None]  # (B,C,w)
    qg = _group(q, ck.shape[2])
    s = _scores(qg, ck, scale)                     # (B,KV,G,C,w)
    s = cm.softcap(s, cfg.logit_softcap)
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = _combine(p, cv, q.dtype)
    return out, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# Cross attention (whisper decoder / mllama image layers)
# ---------------------------------------------------------------------------

def cross_attention(p, cfg: ModelConfig, x, memory=None, *, cache=None,
                    prefix: str = "cross_"):
    """memory: (B, T, D) encoder states (train/prefill); cache: {'ck','cv'}."""
    hd = cfg.resolved_head_dim
    scale = hd ** -0.5
    q = jnp.einsum("bsd,dhk->bshk", x, p[prefix + "wq"])
    if cache is None:
        k = jnp.einsum("btd,dhk->bthk", memory, p[prefix + "wk"])
        v = jnp.einsum("btd,dhk->bthk", memory, p[prefix + "wv"])
    else:
        k, v = cache["ck"], cache["cv"]
    qg = _group(q, k.shape[2])
    s = _scores(qg, k, scale)
    probs = jax.nn.softmax(s, axis=-1)
    out = _combine(probs, v, q.dtype)
    return jnp.einsum("bshk,hkd->bsd", out, p[prefix + "wo"])


def cross_kv(p, cfg: ModelConfig, memory, prefix: str = "cross_"):
    k = jnp.einsum("btd,dhk->bthk", memory, p[prefix + "wk"])
    v = jnp.einsum("btd,dhk->bthk", memory, p[prefix + "wv"])
    return {"ck": k, "cv": v}


def init_kv_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, max_seq: int,
                  dtype):
    w = min(max_seq, spec.window) if spec.window is not None else max_seq
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return {"k": jnp.zeros((batch, w, kv, hd), dtype),
            "v": jnp.zeros((batch, w, kv, hd), dtype)}
