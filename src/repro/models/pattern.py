"""Periodic layer-pattern stack.

A model is ``prefix + unit*n + suffix`` of blocks (configs/base.py).  The
repeated unit lowers as ONE ``lax.scan`` over stacked parameters, so HLO
size is O(|unit|) regardless of depth — 100-layer llama-3.2-vision emits
the same amount of HLO as its 5-block unit.  Prefix/suffix blocks apply
inline.  Caches are stacked with the same structure.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, CROSS, MLA, MOE, NO_FFN, RGLRU, RWKV6,
                                RWKV_CM, LayerSpec, ModelConfig)
from repro.models import attention as attn
from repro.models import common as cm
from repro.models import ffn as ffn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rg_mod
from repro.models import rwkv6 as rwkv_mod


# ---------------------------------------------------------------------------
# Single block
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, spec: LayerSpec) -> Dict[str, Any]:
    dt = cm.dtype_of(cfg.dtype)
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {}
    if spec.attn_norm:
        p["norm1"] = cm.init_norm(cfg.norm, cfg.d_model, dt)
    if spec.mixer == ATTN:
        p["mixer"] = attn.init_attention(ks[0], cfg, spec)
    elif spec.mixer == MLA:
        p["mixer"] = mla_mod.init_mla(ks[0], cfg)
    elif spec.mixer == RGLRU:
        p["mixer"] = rg_mod.init_rglru(ks[0], cfg)
    elif spec.mixer == RWKV6:
        p["mixer"] = rwkv_mod.init_rwkv_tmix(ks[0], cfg)
    elif spec.mixer == CROSS:
        p["mixer"] = attn.init_attention(ks[0], cfg, spec, cross=True)
        p["gate_attn"] = jnp.zeros((), jnp.float32)
        p["gate_ffn"] = jnp.zeros((), jnp.float32)
    else:
        raise ValueError(spec.mixer)
    if spec.cross:  # whisper decoder: self + cross in the same block
        p["cross"] = attn.init_attention(ks[1], cfg, spec, cross=True)
        p["norm_cross"] = cm.init_norm(cfg.norm, cfg.d_model, dt)
    if spec.ffn != NO_FFN:
        p["norm2"] = cm.init_norm(cfg.norm, cfg.d_model, dt)
        if spec.ffn == MOE:
            p["ffn"] = moe_mod.init_moe(ks[2], cfg)
        elif spec.ffn == RWKV_CM:
            p["ffn"] = rwkv_mod.init_rwkv_cmix(ks[2], cfg)
        else:
            p["ffn"] = ffn_mod.init_ffn(ks[2], cfg, spec.ffn)
    if cfg.post_norm:
        p["post_norm1"] = cm.init_norm(cfg.norm, cfg.d_model, dt)
        if spec.ffn != NO_FFN:
            p["post_norm2"] = cm.init_norm(cfg.norm, cfg.d_model, dt)
    return p


def init_block_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     max_seq: int, n_memory: int, dtype) -> Dict[str, Any]:
    c: Dict[str, Any] = {}
    if spec.mixer == ATTN:
        c["mix"] = attn.init_kv_cache(cfg, spec, batch, max_seq, dtype)
    elif spec.mixer == MLA:
        c["mix"] = mla_mod.init_mla_cache(cfg, batch, max_seq, dtype)
    elif spec.mixer == RGLRU:
        c["mix"] = rg_mod.init_rglru_cache(cfg, batch, dtype)
    elif spec.mixer == RWKV6:
        c["mix"] = rwkv_mod.init_rwkv_cache(cfg, batch, dtype)
    elif spec.mixer == CROSS:
        kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        c["mix"] = {"ck": jnp.zeros((batch, n_memory, kv, hd), dtype),
                    "cv": jnp.zeros((batch, n_memory, kv, hd), dtype)}
    if spec.cross:
        kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        c["cross"] = {"ck": jnp.zeros((batch, n_memory, kv, hd), dtype),
                      "cv": jnp.zeros((batch, n_memory, kv, hd), dtype)}
    return c


def _norm(cfg, p, x, gemma_offset=False):
    return cm.apply_norm(cfg.norm, p, x, cfg.norm_eps, gemma_offset)


def apply_block(p, cfg: ModelConfig, spec: LayerSpec, x, positions, *,
                memory=None, cache=None, pos=None, collect=None, kv_len=None):
    """-> (x, new_cache, moe_aux_loss).

    Modes: cache=None,collect=None -> train fwd; cache=None,collect=max_seq
    -> prefill emitting a decode-ready cache; cache set -> one-token decode.
    ``kv_len`` (B,): real lengths of right-padded rows (bidirectional
    layers mask the keys past them).
    """
    want_cache = cache is not None or collect is not None
    new_cache: Dict[str, Any] = {}
    aux = jnp.zeros((), jnp.float32)
    go = cfg.post_norm  # gemma-style (1+w) rmsnorm offset travels with it
    h = _norm(cfg, p["norm1"], x, go) if spec.attn_norm else x

    if spec.mixer == ATTN:
        mix_cache = cache["mix"] if cache is not None else None
        h, nc = attn.self_attention(p["mixer"], cfg, spec, h, positions,
                                    cache=mix_cache, pos=pos, collect=collect,
                                    kv_len=kv_len)
        if nc is not None:
            new_cache["mix"] = nc
    elif spec.mixer == MLA:
        if cache is None:
            h, nc = mla_mod.mla_prefill(p["mixer"], cfg, spec, h, positions,
                                        collect=collect)
        else:
            h, nc = mla_mod.mla_decode(p["mixer"], cfg, spec, h, positions,
                                       cache["mix"], pos)
        if nc is not None:
            new_cache["mix"] = nc
    elif spec.mixer == RGLRU:
        h, nc = rg_mod.rglru_block(p["mixer"], cfg, h,
                                   cache=cache["mix"] if cache else None,
                                   collect=collect is not None)
        if nc is not None:
            new_cache["mix"] = nc
    elif spec.mixer == RWKV6:
        h, nc = rwkv_mod.rwkv_tmix(
            p["mixer"], cfg, h,
            cache=cache["mix"]["tmix"] if cache else None,
            chunk=cfg.attn_chunk if cfg.attn_impl == "chunked" else 0,
            collect=collect is not None)
        if nc is not None:
            new_cache["mix"] = {"tmix": nc}
    elif spec.mixer == CROSS:
        if cache is not None:
            h = attn.cross_attention(p["mixer"], cfg, h, cache=cache["mix"])
            new_cache["mix"] = cache["mix"]
        else:
            if collect is not None:
                new_cache["mix"] = attn.cross_kv(p["mixer"], cfg, memory)
            h = attn.cross_attention(p["mixer"], cfg, h, memory=memory)
        h = h * jnp.tanh(p["gate_attn"]).astype(h.dtype)

    if cfg.post_norm:
        h = _norm(cfg, p["post_norm1"], h, go)
    x = x + h

    if spec.cross:  # whisper decoder cross-attn sublayer
        h = _norm(cfg, p["norm_cross"], x, go)
        if cache is not None:
            h = attn.cross_attention(p["cross"], cfg, h, cache=cache["cross"])
            new_cache["cross"] = cache["cross"]
        else:
            if collect is not None:
                new_cache["cross"] = attn.cross_kv(p["cross"], cfg, memory)
            h = attn.cross_attention(p["cross"], cfg, h, memory=memory)
        x = x + h

    if spec.ffn != NO_FFN:
        h = _norm(cfg, p["norm2"], x, go)
        if spec.ffn == MOE:
            h, aux = moe_mod.apply_moe(p["ffn"], cfg, h)
        elif spec.ffn == RWKV_CM:
            h, nc = rwkv_mod.rwkv_cmix(
                p["ffn"], cfg, h,
                cache=cache["mix"]["cmix"] if cache else None,
                collect=collect is not None)
            if nc is not None:
                new_cache["mix"] = dict(new_cache.get("mix", {}), cmix=nc)
        else:
            h = ffn_mod.apply_ffn(p["ffn"], spec.ffn, h)
        if cfg.post_norm:
            h = _norm(cfg, p["post_norm2"], h, go)
        if spec.mixer == CROSS:
            h = h * jnp.tanh(p["gate_ffn"]).astype(h.dtype)
        x = x + h
    return x, (new_cache if want_cache else None), aux


# ---------------------------------------------------------------------------
# Stack
# ---------------------------------------------------------------------------

def init_stack(key, cfg: ModelConfig) -> Dict[str, Any]:
    prefix, n_units, suffix = cfg.pattern_decomposition()
    kp, ku, ksf = jax.random.split(key, 3)
    params: Dict[str, Any] = {"prefix": [], "unit": [], "suffix": []}
    for i, spec in enumerate(prefix):
        params["prefix"].append(
            init_block(jax.random.fold_in(kp, i), cfg, spec))
    if n_units:
        for i, spec in enumerate(cfg.unit):
            keys = jax.random.split(jax.random.fold_in(ku, i), n_units)
            params["unit"].append(
                jax.vmap(lambda k: init_block(k, cfg, spec))(keys))
    for i, spec in enumerate(suffix):
        params["suffix"].append(
            init_block(jax.random.fold_in(ksf, i), cfg, spec))
    return params


def init_stack_cache(cfg: ModelConfig, batch: int, max_seq: int,
                     n_memory: int, dtype) -> Dict[str, Any]:
    prefix, n_units, suffix = cfg.pattern_decomposition()
    mk = lambda spec: init_block_cache(cfg, spec, batch, max_seq, n_memory, dtype)
    cache: Dict[str, Any] = {
        "prefix": [mk(s) for s in prefix],
        "unit": [],
        "suffix": [mk(s) for s in suffix],
    }
    if n_units:
        for spec in cfg.unit:
            one = mk(spec)
            cache["unit"].append(jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_units,) + a.shape), one))
    return cache


def apply_stack(params, cfg: ModelConfig, x, positions, *, memory=None,
                cache=None, pos=None, collect=None, kv_len=None):
    """-> (x, new_cache | None, total_moe_aux)."""
    prefix, n_units, suffix = cfg.pattern_decomposition()
    want_cache = cache is not None or collect is not None
    aux_total = jnp.zeros((), jnp.float32)
    new_cache = {"prefix": [], "unit": None, "suffix": []} \
        if want_cache else None

    for i, spec in enumerate(prefix):
        x, nc, aux = apply_block(
            params["prefix"][i], cfg, spec, x, positions, memory=memory,
            cache=cache["prefix"][i] if cache else None, pos=pos,
            collect=collect, kv_len=kv_len)
        aux_total += aux
        if want_cache:
            new_cache["prefix"].append(nc)

    if n_units:
        def unit_body(carry, xs):
            h = carry
            u_params = xs[0]
            u_cache = xs[1] if cache is not None else None
            ncs, aux_u = [], jnp.zeros((), jnp.float32)
            for i, spec in enumerate(cfg.unit):
                h, nc, aux = apply_block(
                    u_params[i], cfg, spec, h, positions, memory=memory,
                    cache=u_cache[i] if u_cache is not None else None,
                    pos=pos, collect=collect, kv_len=kv_len)
                ncs.append(nc)
                aux_u += aux
            return h, (ncs, aux_u) if want_cache else aux_u

        body = jax.checkpoint(unit_body) if cfg.remat else unit_body
        if cache is not None:
            x, (unit_caches, auxs) = jax.lax.scan(
                body, x, (params["unit"], cache["unit"]))
            new_cache["unit"] = unit_caches
        elif collect is not None:
            x, (unit_caches, auxs) = jax.lax.scan(
                body, x, (params["unit"],))
            new_cache["unit"] = unit_caches
        else:
            x, auxs = jax.lax.scan(body, x, (params["unit"],))
        aux_total += jnp.sum(auxs)

    for i, spec in enumerate(suffix):
        x, nc, aux = apply_block(
            params["suffix"][i], cfg, spec, x, positions, memory=memory,
            cache=cache["suffix"][i] if cache else None, pos=pos,
            collect=collect, kv_len=kv_len)
        aux_total += aux
        if want_cache:
            new_cache["suffix"].append(nc)

    return x, new_cache, aux_total
