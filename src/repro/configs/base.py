"""Model / input-shape configuration system.

Every assigned architecture is expressed as a ``ModelConfig`` built from a
periodic ``LayerSpec`` pattern (prefix + repeating unit + implicit suffix).
The pattern representation is what lets the transformer stack lower as a
``lax.scan`` over repeat units, keeping HLO size O(|unit|) instead of
O(n_layers) — critical for the 68 dry-run compiles.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Layer specification
# ---------------------------------------------------------------------------

# mixer kinds
ATTN = "attn"            # self attention (GQA/MHA/MQA, optional window)
MLA = "mla"              # DeepSeek multi-head latent attention
RGLRU = "rglru"          # RecurrentGemma recurrent block
RWKV6 = "rwkv6"          # RWKV-6 "Finch" time mix
CROSS = "cross_attn"     # gated cross-attention (mllama image layers)

# ffn kinds
SWIGLU = "swiglu"
GEGLU = "geglu"
GEGLU_ERF = "geglu_erf"  # GeGLU with the exact (erf) GELU (ModernBERT)
GELU_MLP = "gelu_mlp"
MOE = "moe"
RWKV_CM = "rwkv_cm"      # RWKV channel mix
NO_FFN = "none"


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """Static description of one transformer block."""
    mixer: str = ATTN
    ffn: str = SWIGLU
    window: Optional[int] = None      # sliding-window size (None = global)
    rope_theta: Optional[float] = None  # per-layer rope base override
    cross: bool = False               # additional cross-attn (whisper dec)
    causal: bool = True               # False: bidirectional; a window then
                                      # spans |i - j| <= window // 2
    attn_norm: bool = True            # False: no norm before the mixer
                                      # (ModernBERT's first layer)

    @property
    def is_recurrent(self) -> bool:
        return self.mixer in (RGLRU, RWKV6)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_routed: int
    top_k: int
    n_shared: int = 0
    d_ff_expert: int = 0              # routed expert hidden dim
    d_ff_shared: int = 0              # shared expert hidden dim (total)
    router_temperature: float = 1.0   # Thm-2 hook: softmax router temp
    score_func: str = "softmax"       # "softmax" | "sigmoid" (llama4)
    norm_topk: bool = True


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class RGLRUConfig:
    lru_width: int = 0                # 0 -> d_model
    conv_width: int = 4
    c: float = 8.0                    # decay sharpening constant


@dataclasses.dataclass(frozen=True)
class RWKVConfig:
    head_size: int = 64
    decay_lora: int = 64
    mix_lora: int = 32


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """Whisper-style audio encoder (frontend stubbed per carve-out)."""
    n_layers: int = 32
    n_frames: int = 1500              # post-conv frame count
    d_input: int = 1280               # stub embedding dim fed by input_specs


@dataclasses.dataclass(frozen=True)
class VisionConfig:
    """mllama-style vision stub: projector input from a frozen ViT."""
    n_tokens: int = 1601
    d_input: int = 7680               # stub patch-embedding dim


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                 # 0 -> d_model // n_heads
    # layer pattern: prefix layers, then `unit` repeated while it fits,
    # remaining layers continue the unit pattern as an inline suffix.
    prefix: Tuple[LayerSpec, ...] = ()
    unit: Tuple[LayerSpec, ...] = (LayerSpec(),)
    norm: str = "rmsnorm"             # rmsnorm | layernorm | layernorm_nobias
    norm_eps: float = 1e-6
    post_norm: bool = False           # gemma-style post-block norms
    qk_norm: bool = False             # gemma3 qk rmsnorm
    rope_theta: float = 10_000.0
    partial_rotary: float = 1.0       # stablelm: 0.25
    embed_scale: bool = False         # gemma: x * sqrt(d_model)
    tie_embeddings: bool = False
    logit_softcap: float = 0.0
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    rglru: Optional[RGLRUConfig] = None
    rwkv: Optional[RWKVConfig] = None
    encoder: Optional[EncoderConfig] = None
    vision: Optional[VisionConfig] = None
    embed_norm: bool = False          # norm right after the token embedding
    max_positions: int = 0            # longest input an encoder takes (0: any)
    dtype: str = "bfloat16"
    source: str = ""                  # citation from the assignment pool
    # runtime/perf toggles (see EXPERIMENTS.md §Perf)
    attn_impl: str = "full"           # full | chunked (online-softmax scan)
    attn_chunk: int = 1024            # KV chunk for chunked attention
    window_prefill_banded: bool = False  # banded (O(S*w)) windowed prefill
    moe_impl: str = "dense"           # dense | dispatch | sort | ep
    remat: bool = False               # checkpoint each repeat unit
    decode_kernel: bool = False       # flash-decoding Pallas kernel for
                                      # one-token GQA attention (TPU target;
                                      # interpret=True on CPU)

    # ---- derived ----------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def layer_specs(self) -> Tuple[LayerSpec, ...]:
        """Full per-layer spec list (prefix + repeated unit, truncated)."""
        specs = list(self.prefix)
        i = 0
        while len(specs) < self.n_layers:
            specs.append(self.unit[i % len(self.unit)])
            i += 1
        return tuple(specs[: self.n_layers])

    def pattern_decomposition(self) -> Tuple[Tuple[LayerSpec, ...], int, Tuple[LayerSpec, ...]]:
        """(prefix, n_units, suffix) with n_layers == |prefix| + n_units*|unit| + |suffix|."""
        body = self.n_layers - len(self.prefix)
        n_units = body // len(self.unit)
        n_suffix = body - n_units * len(self.unit)
        suffix = tuple(self.unit[i] for i in range(n_suffix))
        return self.prefix, n_units, suffix

    def param_count(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS = 6*N*D)."""
        d, hd = self.d_model, self.resolved_head_dim
        n = self.vocab_size * d                     # tok embed
        if not self.tie_embeddings:
            n += self.vocab_size * d                # lm head
        for spec in self.layer_specs():
            n += self._mixer_params(spec, d, hd)
            n += self._ffn_params(spec, d)
            n += 2 * d                              # pre norms
            if self.post_norm:
                n += 2 * d
        n += d                                      # final norm
        if self.encoder is not None:
            e = self.encoder
            n += e.n_layers * (4 * d * self.n_heads * hd // self.n_heads * self.n_heads // self.n_heads)
            # encoder layers: qkv+o (4*d*d) + mlp (2*d*ff) + norms
            n += e.n_layers * (4 * d * d + 2 * d * self.d_ff + 4 * d)
            n += e.d_input * d                      # stub projector
        if self.vision is not None:
            n += self.vision.d_input * d            # projector
        return n

    def _mixer_params(self, spec: LayerSpec, d: int, hd: int) -> int:
        if spec.mixer == ATTN:
            n = d * self.n_heads * hd + self.n_heads * hd * d  # wq, wo
            n += 2 * d * self.n_kv_heads * hd                  # wk, wv
            if spec.cross:
                n += d * self.n_heads * hd + self.n_heads * hd * d
                n += 2 * d * self.n_kv_heads * hd + d          # + cross norm
            return n
        if spec.mixer == MLA:
            m = self.mla
            qd = m.qk_nope_head_dim + m.qk_rope_head_dim
            n = d * self.n_heads * qd                          # w_q
            n += d * (m.kv_lora_rank + m.qk_rope_head_dim)     # w_dkv
            n += m.kv_lora_rank * self.n_heads * (m.qk_nope_head_dim + m.v_head_dim)
            n += self.n_heads * m.v_head_dim * d               # w_o
            return n
        if spec.mixer == RGLRU:
            w = self.rglru.lru_width or d
            return 2 * d * w + self.rglru.conv_width * w + 2 * w * w + w * d + 2 * w
        if spec.mixer == RWKV6:
            r = self.rwkv
            n = 4 * d * d + d * d                   # r,k,v,g,o
            n += 5 * d + d                          # mix mus + mu_x
            n += 5 * (d * r.mix_lora + r.mix_lora * d)
            n += d * r.decay_lora + r.decay_lora * d + d  # decay lora + w0
            n += 2 * d                              # u ("bonus") + ln
            return n
        if spec.mixer == CROSS:
            n = d * self.n_heads * hd + self.n_heads * hd * d
            n += 2 * d * self.n_kv_heads * hd + 2   # gates
            return n
        raise ValueError(spec.mixer)

    def _ffn_params(self, spec: LayerSpec, d: int) -> int:
        if spec.ffn in (SWIGLU, GEGLU, GEGLU_ERF):
            return 3 * d * self.d_ff
        if spec.ffn == GELU_MLP:
            return 2 * d * self.d_ff
        if spec.ffn == RWKV_CM:
            return d * self.d_ff + self.d_ff * d + 2 * d
        if spec.ffn == MOE:
            m = self.moe
            n = m.n_routed * 3 * d * m.d_ff_expert + d * m.n_routed
            if m.n_shared:
                n += 3 * d * m.d_ff_shared
            return n
        if spec.ffn == NO_FFN:
            return 0
        raise ValueError(spec.ffn)

    def encoder_param_count(self) -> int:
        """Params of the (whisper-style) encoder stack alone."""
        if self.encoder is None:
            return 0
        e = self.encoder
        d = self.d_model
        n = e.n_layers * (4 * d * d + 2 * d * self.d_ff + 4 * d)
        n += e.d_input * d
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top-k + shared experts)."""
        if self.moe is None:
            return self.param_count()
        n = self.param_count()
        m = self.moe
        for spec in self.layer_specs():
            if spec.ffn == MOE:
                n -= (m.n_routed - m.top_k) * 3 * self.d_model * m.d_ff_expert
        return n


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                         # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


def smoke_variant(cfg: ModelConfig) -> ModelConfig:
    """Reduced same-family variant: ≤2 layers, d_model ≤ 512, ≤4 experts."""
    d = min(cfg.d_model, 256)
    heads = 4
    kv = max(1, min(cfg.n_kv_heads, 2 if cfg.n_kv_heads < cfg.n_heads else 4))
    hd = d // heads
    changes = dict(
        n_layers=min(cfg.n_layers, 2 if not cfg.prefix else 2),
        d_model=d, n_heads=heads, n_kv_heads=kv, head_dim=hd,
        d_ff=min(cfg.d_ff, 512), vocab_size=min(cfg.vocab_size, 512),
        dtype="float32",
    )
    if cfg.prefix:
        changes["prefix"] = cfg.prefix[:1]
        changes["n_layers"] = 2
    if cfg.moe is not None:
        changes["moe"] = dataclasses.replace(
            cfg.moe, n_routed=4, top_k=min(cfg.moe.top_k, 2),
            n_shared=min(cfg.moe.n_shared, 1),
            d_ff_expert=128, d_ff_shared=128)
    if cfg.mla is not None:
        changes["mla"] = MLAConfig(kv_lora_rank=64, qk_nope_head_dim=32,
                                   qk_rope_head_dim=16, v_head_dim=32)
        changes["head_dim"] = 32
    if cfg.rglru is not None:
        changes["rglru"] = dataclasses.replace(cfg.rglru, lru_width=d)
    if cfg.rwkv is not None:
        changes["rwkv"] = RWKVConfig(head_size=32, decay_lora=16, mix_lora=8)
    if cfg.encoder is not None:
        changes["encoder"] = EncoderConfig(n_layers=2, n_frames=16, d_input=64)
    if cfg.vision is not None:
        changes["vision"] = VisionConfig(n_tokens=16, d_input=96)
    # shrink windows so smoke sequences exercise the masking paths
    def shrink(spec: LayerSpec) -> LayerSpec:
        if spec.window is not None:
            return dataclasses.replace(spec, window=8)
        return spec
    changes["unit"] = tuple(shrink(s) for s in cfg.unit)
    if cfg.prefix:
        changes["prefix"] = tuple(shrink(s) for s in changes["prefix"])
    return dataclasses.replace(cfg, **changes)
