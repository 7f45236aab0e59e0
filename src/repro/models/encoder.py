"""Bidirectional text encoder (ModernBERT) over the ``models/`` blocks.

``x = LN(E[tok])``, then the pattern stack of bidirectional blocks
(global layers attend to the whole row, local layers to the band
``|i - j| <= window // 2``; keys past a row's length are masked), then
the final norm of the CLS row (position 0), L2-normalized in float32.

Weights, from one key by this recipe (the plain references redraw them
from it): ``PRNGKey(seed)`` split in two, the first for the token
embedding (a normal times 0.02), the second for ``pattern.init_stack``
(split in three: prefix, unit, suffix; prefix layer i from
``fold_in(prefix key, i)``, unit slot j of every repeat from
``split(fold_in(unit key, j), n_units)``).  A block's key splits in six:
attention from the first (split in eight: wq, wk, wv, wo), the
feed-forward from the third (split in three: gate, up, down).  Dense
weights are truncated normals on [-2, 2] over the square root of the
first axis (heads, for wo), norms start at one.  Each is drawn in
float32 and cast to the configuration's dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common as cm
from repro.models import pattern


def init_encoder(key, cfg: ModelConfig):
    dt = cm.dtype_of(cfg.dtype)
    k_emb, k_stack = jax.random.split(key)
    params = {"tok_embed": cm.embed_init(k_emb, (cfg.vocab_size,
                                                 cfg.d_model), dt),
              "stack": pattern.init_stack(k_stack, cfg),
              "final_norm": cm.init_norm(cfg.norm, cfg.d_model, dt)}
    if cfg.embed_norm:
        params["embed_norm"] = cm.init_norm(cfg.norm, cfg.d_model, dt)
    return params


def encode(params, cfg: ModelConfig, tokens, lengths):
    """tokens (B, S) int32, right-padded past ``lengths`` (B,) ->
    (B, d_model) float32 unit CLS embeddings.  No number is shared
    between rows."""
    x = cm.take_embedding(params["tok_embed"], tokens)
    if cfg.embed_norm:
        x = cm.apply_norm(cfg.norm, params["embed_norm"], x, cfg.norm_eps)
    positions = jnp.broadcast_to(
        jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape)
    x, _, _ = pattern.apply_stack(params["stack"], cfg, x, positions,
                                  kv_len=lengths)
    cls = cm.apply_norm(cfg.norm, params["final_norm"], x[:, 0],
                        cfg.norm_eps).astype(jnp.float32)
    return cls / jnp.maximum(
        jnp.linalg.norm(cls, axis=-1, keepdims=True), 1e-12)
