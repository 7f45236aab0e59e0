"""Operations and bytes each layer's work needs, from the configuration's
shapes, whatever kernel happens to run it.

Decoders (one token at context position ``ctx``): every matrix product
once (Q, K, V, O, the SwiGLU gate, up and down, and the LM head), two
FLOPs a multiply-add, plus attention's QK and PV products over ``ctx``
keys.  A decode step must read every weight once and the live keys and
values of each row, and write one position per row.

Routing: the flat task scores every query against every route (2·B·N·D
FLOPs, N·D store bytes).  The two-stage IVF task scores the slab heads
and then ``nprobe`` slabs of ``slab_k`` rows per query.
"""
from __future__ import annotations

from typing import Dict

_BYTES = {"bfloat16": 2, "float32": 4}


def matmul_params(s: Dict) -> int:
    d, h, kv, hd, f = (s["d_model"], s["n_heads"], s["n_kv_heads"],
                       s["head_dim"], s["d_ff"])
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return s["n_layers"] * per_layer + s["vocab_size"] * d


def weight_bytes(s: Dict) -> int:
    return matmul_params(s) * _BYTES[s["dtype"]]


def kv_bytes_per_token(s: Dict) -> int:
    return s["n_layers"] * 2 * s["n_kv_heads"] * s["head_dim"] \
        * _BYTES[s["dtype"]]


def token_flops(s: Dict, ctx: float) -> float:
    """FLOPs for one token attending to ``ctx`` positions."""
    return 2.0 * matmul_params(s) \
        + 4.0 * s["n_layers"] * s["n_heads"] * s["head_dim"] * ctx


def prefill_flops(s: Dict, n: int) -> float:
    """FLOPs for a prompt of ``n`` tokens (token t attends to t keys)."""
    return 2.0 * matmul_params(s) * n \
        + 4.0 * s["n_layers"] * s["n_heads"] * s["head_dim"] * n * (n + 1) / 2


def decode_step_bytes(s: Dict, rows: int, live_positions: int) -> float:
    """Least bytes one pooled decode step moves: every weight, the live
    keys and values of its rows, one new position per row."""
    kv = kv_bytes_per_token(s)
    return weight_bytes(s) + kv * live_positions + kv * rows


def flat_route(b: float, n: int, d: int, store_bytes: int) -> Dict[str, float]:
    return {"flops": 2.0 * b * n * d, "bytes": float(store_bytes)}


def ivf_route(b: float, layout: Dict) -> Dict[str, float]:
    d = layout["dim"]
    rows = layout["nprobe"] * layout["slab_k"]
    return {"flops": 2.0 * b * (layout["n_slabs"] + rows) * d,
            "bytes": 4.0 * layout["n_slabs"] * d
            + b * rows * layout["slab_row_bytes"]}


def roofline_s(work: Dict[str, float], peak: Dict[str, float]) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(work["flops"] / peak["bf16_flops"],
               work["bytes"] / peak["hbm_bytes_per_s"])
