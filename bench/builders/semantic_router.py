"""Builder for policies in the shape of the vLLM Semantic Router's
``config.yaml``: category signals in one softmax_exclusive group, guard
classifiers, category routes onto backends.

Everything comes from the configuration file: categories with their
candidate phrases (which the embedder turns into centroids), the words
the traffic writes prompts from, guards, backends and serving sizes.
"""
from __future__ import annotations

import zlib
from typing import Any, Dict, List

import numpy as np

from bench import traffic
from bench.builders import Built
from bench.reference.hash_embed import RefHashEmbedder
from bench.reference.router import RefPolicy, RefRule


def _backend_name(base: str, seed: int) -> str:
    # the program seeds a backend's weights from its name, so the name
    # carries the run's seed
    return f"{base}-{seed}"


def _dsl(cfg: Dict[str, Any], seed: int) -> str:
    grp = cfg["group"]
    cats = cfg["categories"]
    parts = []
    for c in cats:
        parts.append(f'SIGNAL embedding {c["name"]} {{ candidates: '
                     f'{_list(c["candidates"])} }}')
    parts.append(
        "SIGNAL_GROUP categories { semantics: softmax_exclusive "
        f'temperature: {grp["temperature"]} threshold: {grp["threshold"]} '
        f'members: [{", ".join(c["name"] for c in cats)}] '
        f'default: {grp["default"]} }}')
    for g in cfg["guards"]:
        parts.append(f'SIGNAL {g["type"]} {g["name"]} {{ threshold: '
                     f'{g["threshold"]} candidates: {_list(g["candidates"])} }}')
    for g in cfg["guards"]:
        parts.append(
            f'ROUTE guard_{g["name"]} {{ PRIORITY {g["priority"]} TIER 2 '
            f'WHEN {g["type"]}("{g["name"]}") MODEL '
            f'"{_backend_name(g["backend"], seed)}" }}')
    for c in cats:
        parts.append(
            f'ROUTE route_{c["name"]} {{ PRIORITY {c["priority"]} WHEN '
            f'embedding("{c["name"]}") MODEL '
            f'"{_backend_name(c["backend"], seed)}" }}')
    for b in cfg["backends"]:
        parts.append(f'BACKEND {_backend_name(b["name"], seed)} {{ arch: '
                     f'"{b["arch"]}" size: "{b["size"]}" max_seq: '
                     f'{cfg["max_seq"]} }}')
    parts.append(f'GLOBAL {{ default_model: '
                 f'"{_backend_name(cfg["default_backend"], seed)}" }}')
    return "\n".join(parts)


def _list(xs: List[str]) -> str:
    return "[" + ", ".join(f'"{x}"' for x in xs) + "]"


def _ref_policy(cfg: Dict[str, Any], emb: RefHashEmbedder) -> RefPolicy:
    sigs = [(c["name"], c["candidates"]) for c in cfg["categories"]]
    sigs += [(g["name"], g["candidates"]) for g in cfg["guards"]]
    rows = []
    for _, cands in sigs:
        c = emb.embed(cands).mean(axis=0)
        rows.append(c / max(np.linalg.norm(c), 1e-8))
    rules = [RefRule(f'guard_{g["name"]}', g["name"], 2, g["priority"])
             for g in cfg["guards"]]
    rules += [RefRule(f'route_{c["name"]}', c["name"], 1, c["priority"])
              for c in cfg["categories"]]
    return RefPolicy(
        names=[n for n, _ in sigs], centroids=np.stack(rows),
        group=[c["name"] for c in cfg["categories"]],
        temperature=cfg["group"]["temperature"],
        group_threshold=cfg["group"]["threshold"],
        default=cfg["group"]["default"],
        classifier={g["name"]: g["threshold"] for g in cfg["guards"]},
        rules=rules)


def _words(cfg: Dict[str, Any]) -> Dict[str, List[List[str]]]:
    """Tenant -> word pools of the categories or guards it writes from."""
    out: Dict[str, List[List[str]]] = {}
    for c in cfg["categories"]:
        out.setdefault(c["family"], []).append(c["words"])
    for g in cfg["guards"]:
        if g.get("words"):
            out.setdefault(g["name"], []).append(g["words"])
    return out


def texts_for(cfg: Dict[str, Any], items: List[traffic.Item],
              rng: np.random.Generator, tag: str) -> List[str]:
    """One unique prompt per item, of exactly its byte length, written
    from a category (or guard) of the item's tenant."""
    pools = _words(cfg)
    out = []
    for it in items:
        opts = pools[it.tenant]
        words = opts[int(rng.integers(len(opts)))]
        out.append(traffic.fit_text(words, f"{tag}{it.seq}", it.prompt_bytes,
                                    rng))
    return out


def build(cfg: Dict[str, Any], seed: int, items: List[traffic.Item],
          rng: np.random.Generator) -> Built:
    backends = {b["name"]: dict(b, name=_backend_name(b["name"], seed),
                                seed=zlib.crc32(_backend_name(
                                    b["name"], seed).encode()) & 0xFFFF)
                for b in cfg["backends"]}

    def reference():
        emb = RefHashEmbedder(dim=cfg["embedder"]["dim"],
                              n_buckets=cfg["embedder"]["n_buckets"],
                              seed=cfg["embedder"]["seed"])
        return _ref_policy(cfg, emb), emb.embed

    return Built(
        dsl=_dsl(cfg, seed),
        embedder_dim=cfg["embedder"]["dim"],
        embedder=None,
        texts=texts_for(cfg, items, rng, f"#{seed % 100000}-"),
        make_texts=lambda its, r, tag: texts_for(cfg, its, r, tag),
        reference=reference,
        backends=list(backends.values()),
        service=dict(slots=cfg["slots"],
                     prefill_chunk=cfg.get("prefill_chunk")),
        tie_margin=cfg["tie_margin"])
