"""Text encoders that embed a request for the router's signals.

Encoders are kept apart from ``archs.ALL``: those are decoders, and the
tests parametrized over ``ALL`` prefill, decode and cache them, which an
encoder does not do.  ``signals.embedder.EncoderEmbedder`` runs one of
these on the device.
"""
from __future__ import annotations

from repro.configs.base import ATTN, GEGLU_ERF, LayerSpec, ModelConfig


def modernbert(*, name: str, hidden_size: int, num_hidden_layers: int,
               num_attention_heads: int, intermediate_size: int,
               vocab_size: int, max_position_embeddings: int,
               global_attn_every_n_layers: int = 3,
               local_attention: int = 128,
               global_rope_theta: float = 160_000.0,
               local_rope_theta: float = 10_000.0, norm_eps: float = 1e-5,
               dtype: str = "bfloat16", attn_chunk: int = 512,
               source: str = "") -> ModelConfig:
    """A ModernBERT encoder from its published config's numbers (the
    Hugging Face keys).  Layer i is global when ``i %
    global_attn_every_n_layers == 0`` (theta ``global_rope_theta``),
    else local, keys ``|i - j| <= local_attention // 2`` (theta
    ``local_rope_theta``); no biases; a norm after the embedding;
    layer 0 has none before its attention; exact-erf GeGLU, ``Wi``
    hidden -> 2 x intermediate; CLS pooled.  Global key chunks of
    ``attn_chunk`` bound the live scores (models/attention.py)."""
    glob = LayerSpec(mixer=ATTN, ffn=GEGLU_ERF, causal=False,
                     rope_theta=global_rope_theta)
    local = LayerSpec(mixer=ATTN, ffn=GEGLU_ERF, causal=False,
                      window=local_attention, rope_theta=local_rope_theta)
    return ModelConfig(
        name=name, family="encoder", n_layers=num_hidden_layers,
        d_model=hidden_size, n_heads=num_attention_heads,
        n_kv_heads=num_attention_heads,
        head_dim=hidden_size // num_attention_heads, d_ff=intermediate_size,
        vocab_size=vocab_size,
        prefix=(LayerSpec(mixer=ATTN, ffn=GEGLU_ERF, causal=False,
                          rope_theta=global_rope_theta, attn_norm=False),),
        unit=(local,) * (global_attn_every_n_layers - 1) + (glob,),
        norm="layernorm_nobias", norm_eps=norm_eps, embed_norm=True,
        tie_embeddings=True, max_positions=max_position_embeddings,
        attn_chunk=attn_chunk, dtype=dtype, source=source)


# ModernBERT-base, as Alibaba-NLP/gte-modernbert-base embeds with it
# (768-d, CLS pooling, 8,192 positions); bf16 here, the published
# checkpoint is float32
MODERNBERT_BASE = modernbert(
    name="modernbert-base", hidden_size=768, num_hidden_layers=22,
    num_attention_heads=12, intermediate_size=1152, vocab_size=50_368,
    max_position_embeddings=8192,
    source="arXiv:2412.13663 (hf answerdotai/ModernBERT-base, "
           "Alibaba-NLP/gte-modernbert-base)")

