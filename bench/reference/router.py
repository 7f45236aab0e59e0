"""Plain reference of the routing layers, in float64 numpy.

It computes, from data the benchmark made itself (centroids, thresholds,
the policy's rules), what a router must decide for a batch of query
embeddings: cosine scores, the softmax_exclusive group (softmax over the
members at the group temperature, fire above the group threshold, the
declared default when no member fires), classifier signals ((cos + 1) / 2
against their threshold), and the policy argmax over fired rules by
(tier, priority, confidence).  It imports nothing of the program.

``precision`` picks how the score matrix is formed: ``"f64"`` on the
host (the reference), or a float32 ``jnp`` dot at a JAX precision such
as ``"high"`` (the lower-precision control, run on the device).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np


@dataclasses.dataclass
class RefRule:
    name: str
    signal: str
    tier: int
    priority: int


@dataclasses.dataclass
class RefPolicy:
    """A policy as plain data: signal centroids (unit rows), one optional
    softmax_exclusive group, classifier signals, single-signal rules."""
    names: List[str]                       # signal name per centroid row
    centroids: np.ndarray                  # (N, D) float64, unit rows
    group: List[str]                       # members of the one group
    temperature: float
    group_threshold: float
    default: Optional[str]
    classifier: Dict[str, float]           # signal -> threshold
    rules: List[RefRule]


@dataclasses.dataclass
class Decisions:
    route: List[str]                       # rule name or "__default__"
    score: np.ndarray                      # winner confidence, -inf default
    margin: np.ndarray                     # nearest firing decision's gap


def _scores(emb: np.ndarray, cent: np.ndarray, precision: str) -> np.ndarray:
    """(B, N) cosine scores: float64 for the reference; ``"high"`` is
    float32 at three bf16 passes (hi*hi + hi*lo + lo*hi, float32
    accumulation), written out so it reads the same on every platform."""
    if precision == "f64":
        return np.asarray(emb, np.float64) @ np.asarray(cent, np.float64).T
    import jax
    import jax.numpy as jnp
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    a = jnp.asarray(emb, jnp.float32)
    b = jnp.asarray(cent, jnp.float32).T
    a_hi = a.astype(jnp.bfloat16)
    b_hi = b.astype(jnp.bfloat16)
    a_lo = (a - a_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    b_lo = (b - b_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    dot = lambda x, y: jnp.dot(x, y, preferred_element_type=jnp.float32)
    s = dot(a_hi, b_hi) + dot(a_hi, b_lo) + dot(a_lo, b_hi)
    return np.asarray(jax.device_get(s), np.float64)


def decide(policy: RefPolicy, emb: np.ndarray,
           precision: str = "f64") -> Decisions:
    """Reference decisions for query embeddings ``emb`` (B, D)."""
    sims = _scores(emb, policy.centroids, precision)
    b = sims.shape[0]
    col = {n: i for i, n in enumerate(policy.names)}
    fired: Dict[str, np.ndarray] = {}
    conf: Dict[str, np.ndarray] = {}
    margin = np.full(b, np.inf)
    if policy.group:
        g = np.array([col[m] for m in policy.group])
        z = sims[:, g] / policy.temperature
        z -= z.max(axis=1, keepdims=True)
        p = np.exp(z)
        p /= p.sum(axis=1, keepdims=True)
        f = p > policy.group_threshold
        margin = np.minimum(margin, np.abs(p - policy.group_threshold)
                            .min(axis=1))
        none = ~f.any(axis=1)
        for k, m in enumerate(policy.group):
            fired[m] = f[:, k] | (none if m == policy.default else False)
            conf[m] = np.where(fired[m], p[:, k], 0.0)
    for name, thr in policy.classifier.items():
        raw = (sims[:, col[name]] + 1.0) / 2.0
        fired[name] = raw >= thr
        conf[name] = np.where(fired[name], raw, 0.0)
        margin = np.minimum(margin, np.abs(raw - thr))
    best_key = [None] * b
    route = ["__default__"] * b
    score = np.full(b, -np.inf)
    for r in policy.rules:
        f, c = fired[r.signal], np.clip(conf[r.signal], 0.0, 1.0)
        for i in np.flatnonzero(f):
            key = (r.tier, r.priority, c[i])
            if best_key[i] is None or key > best_key[i]:
                best_key[i] = key
                route[i] = r.name
                score[i] = c[i]
    return Decisions(route, score, margin)


def compare(got_route: Sequence[str], got_score: np.ndarray,
            ref: Decisions, tie_margin: float) -> Dict[str, float]:
    """The routing numbers that decide ``correct``.

    * ``route_score_gap``: widest |score - reference score| over the
      requests both sides route alike (default routes score -inf on
      both and count 0), and ``route_score_mae`` the mean of it;
    * ``route_miss_share``: share of requests routed otherwise than the
      reference, leaving out near-ties (the reference's nearest firing
      decision within ``tie_margin`` of its threshold).
    """
    got_score = np.asarray(got_score, np.float64)
    gaps = []
    miss = 0
    for i, (r, want) in enumerate(zip(got_route, ref.route)):
        if r == want:
            if np.isfinite(ref.score[i]) or np.isfinite(got_score[i]):
                gaps.append(abs(got_score[i] - ref.score[i]))
            else:
                gaps.append(0.0)
        elif ref.margin[i] > tie_margin:
            miss += 1
    return {"route_score_gap": float(max(gaps, default=0.0)),
            "route_score_mae": float(np.mean(gaps)) if gaps else 0.0,
            "route_miss_share": miss / max(1, len(ref.route))}
