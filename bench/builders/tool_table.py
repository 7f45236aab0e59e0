"""Builder for large tool/intent tables: one route per tool, every route
in one softmax_exclusive group, queries that are fresh vectors near a
tool's centroid.

The table follows the recipe of ``repro.analysis.tables.planted_cap_table``
(copied here, without planted conflicts, so the yardstick cannot move):
``n_topics`` unit topic centers, each route a unit vector at the topic
scatter ``topic_tau`` around its topic's center.  Queries sit at
``query_tau`` around a uniformly drawn route.  The embedder maps each
name to its vector, so embedding costs one table lookup.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from bench import traffic
from bench.builders import Built
from bench.reference.router import RefPolicy, RefRule


class TableEmbedder:
    """Embeds a name as the vector the benchmark drew for it."""

    def __init__(self, vectors: Dict[str, np.ndarray], dim: int):
        self.vectors = vectors
        self.dim = dim

    def embed(self, texts):
        return np.stack([self.vectors[t] for t in texts]).astype(np.float32)


def _unit(x: np.ndarray) -> np.ndarray:
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def table(cfg: Dict[str, Any], seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    n, d = cfg["n_routes"], cfg["dim"]
    centers = _unit(rng.standard_normal((cfg["n_topics"], d)))
    topic = rng.integers(0, cfg["n_topics"], size=n)
    noise = _unit(rng.standard_normal((n, d)))
    return _unit(centers[topic] + cfg["topic_tau"] * noise)


def _sig(i: int) -> str:
    return f"t{i:06d}"


def _dsl(cfg: Dict[str, Any]) -> str:
    n = cfg["n_routes"]
    names = [_sig(i) for i in range(n)]
    grp = cfg["group"]
    parts = [f'SIGNAL embedding {s} {{ candidates: ["{s}"] threshold: '
             f'{cfg["signal_threshold"]} }}' for s in names]
    parts.append(
        "SIGNAL_GROUP tools { semantics: softmax_exclusive temperature: "
        f'{grp["temperature"]} threshold: {grp["threshold"]} members: '
        f'[{", ".join(names)}] default: {names[0]} }}')
    parts += [f'ROUTE r{i:06d} {{ PRIORITY {100 + i} WHEN embedding("{s}") '
              f'MODEL "m{i % 2}" }}' for i, s in enumerate(names)]
    parts.append('GLOBAL { default_model: "m0" }')
    return "\n".join(parts)


def build(cfg: Dict[str, Any], seed: int, items: List[traffic.Item],
          rng: np.random.Generator) -> Built:
    cent = table(cfg, seed)
    n, d = cent.shape
    vectors = {_sig(i): cent[i].astype(np.float32) for i in range(n)}
    emb = TableEmbedder(vectors, d)

    def make_texts(its, r, tag):
        picks = r.integers(0, n, size=len(its))
        q = _unit(cent[picks] + cfg["query_tau"]
                  * _unit(r.standard_normal((len(its), d))))
        out = []
        for it, v in zip(its, q):
            name = f"{tag}{it.seq}"
            vectors[name] = v.astype(np.float32)
            out.append(name)
        return out

    texts = make_texts(items, rng, "q")
    grp = cfg["group"]

    def reference():
        return ref, lambda ts: np.stack([vectors[t] for t in ts]
                                        ).astype(np.float64)

    ref = RefPolicy(
        names=[_sig(i) for i in range(n)],
        # the router normalizes float32 centroids; the reference takes
        # the same float32 vectors the benchmark handed it
        centroids=_unit(cent.astype(np.float32).astype(np.float64)),
        group=[_sig(i) for i in range(n)],
        temperature=grp["temperature"], group_threshold=grp["threshold"],
        default=_sig(0), classifier={},
        rules=[RefRule(f"r{i:06d}", _sig(i), 1, 100 + i) for i in range(n)])
    return Built(
        dsl=_dsl(cfg), embedder_dim=d, embedder=emb, texts=texts,
        make_texts=make_texts, reference=reference,
        backends=[], service=dict(validate=False),
        tie_margin=cfg["tie_margin"])
