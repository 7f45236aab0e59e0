"""Operations of the text encoder's work, from the configuration's
numbers (the Hugging Face keys), whatever lowering runs it.

A sequence of n real tokens costs every matrix product once per token,
``2 P n`` for the ``P`` weights of the layers (fused Q/K/V, output,
the GeGLU's input and output), plus attention's QK and PV products,
``4 H hd`` a token and key: ``n`` keys in a global layer, ``min(n,
local_attention + 1)`` in a local one.  Padding is not work.
"""
from __future__ import annotations

from typing import Dict, Iterable


def matmul_params(s: Dict) -> int:
    d, f = s["hidden_size"], s["intermediate_size"]
    return s["num_hidden_layers"] * (3 * d * d + d * d + 2 * d * f + f * d)


def layer_counts(s: Dict) -> Dict[str, int]:
    g = s["global_attn_every_n_layers"]
    n_global = len(range(0, s["num_hidden_layers"], g))
    return {"global": n_global,
            "local": s["num_hidden_layers"] - n_global}


def sequence_flops(s: Dict, n: int) -> float:
    d = s["hidden_size"]                   # heads x head width
    c = layer_counts(s)
    return (2.0 * matmul_params(s) * n + 4.0 * d * n * n * c["global"]
            + 4.0 * d * n * min(n, s["local_attention"] + 1) * c["local"])


def least_s(s: Dict, lengths: Iterable[int], peak: Dict[str, float]
            ) -> float:
    """Least time the chip could take for sequences of these lengths:
    their FLOPs at the bf16 peak (the weights are read once a launch,
    which is far less time at these lengths)."""
    return sum(sequence_flops(s, n) for n in lengths) / peak["bf16_flops"]
