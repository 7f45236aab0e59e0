"""Builder for a tool table embedded by an on-device text encoder.

One route per tool, every route in one softmax_exclusive group (as in
``tool_table``), but each route's signal now has a text candidate: a
16-48-word tool description drawn from its topic's seeded word list,
which the engine embeds at bind through the encoder.  A query is a
drawn route's description followed by filler from its topic's words, to
exactly the drawn length in tokens (the mix's ``prompt_bytes``, read as
tokens: the words and the [CLS] and [SEP] the tokenizer adds).

``build`` makes only texts; the encoder's weights and every embedding
are made by the entry (``bench/entries/encode_route.py``) and the
reference (``bench/reference/encoder.py``).
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from bench import traffic
from bench.builders import Built
from bench.reference.router import RefPolicy, RefRule

# the encoder's published numbers, under the Hugging Face config's keys
ARCH = ("hidden_size", "num_hidden_layers", "num_attention_heads",
        "intermediate_size", "vocab_size", "max_position_embeddings",
        "global_attn_every_n_layers", "local_attention",
        "global_rope_theta", "local_rope_theta", "norm_eps", "dtype")


def weights_seed(seed: int) -> int:
    """The encoder's weights and tokenizer seed, from the run's seed."""
    return seed % (2**31 - 1)


def _sig(i: int) -> str:
    return f"t{i:06d}"


def topic_words(cfg: Dict[str, Any], seed: int) -> List[np.ndarray]:
    """``n_topics`` lists of ``topic_words`` distinct lowercase words,
    no word in two topics."""
    rng = np.random.default_rng(seed)
    need = cfg["n_topics"] * cfg["topic_words"]
    seen, words = set(), []
    while len(words) < need:
        for n in rng.integers(4, 10, size=need):
            w = "".join(chr(97 + c) for c in rng.integers(0, 26, size=n))
            if w not in seen:
                seen.add(w)
                words.append(w)
    words = np.array(words[:need])
    return list(words.reshape(cfg["n_topics"], cfg["topic_words"]))


def descriptions(cfg: Dict[str, Any], seed: int, topics: List[np.ndarray]):
    """(description per route, topic per route)."""
    rng = np.random.default_rng(seed + 1)
    lo, hi = cfg["desc_words"]
    topic = rng.integers(0, cfg["n_topics"], size=cfg["n_routes"])
    out = [" ".join(topics[t][rng.integers(0, len(topics[t]),
                                           size=rng.integers(lo, hi + 1))])
           for t in topic]
    return out, topic


def _dsl(cfg: Dict[str, Any], desc: List[str]) -> str:
    names = [_sig(i) for i in range(len(desc))]
    grp = cfg["group"]
    parts = [f'SIGNAL embedding {s} {{ candidates: ["{d}"] threshold: '
             f'{cfg["signal_threshold"]} }}' for s, d in zip(names, desc)]
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
    topics = topic_words(cfg, seed)
    desc, topic = descriptions(cfg, seed, topics)
    n = len(desc)

    def make_texts(its, r, tag):
        out = []
        for it, pick in zip(its, r.integers(0, n, size=len(its))):
            words = topics[topic[pick]]
            fill = max(0, it.prompt_bytes - 2 - len(desc[pick].split()))
            out.append(" ".join([desc[pick]] + words[
                r.integers(0, len(words), size=fill)].tolist()))
        return out

    texts = make_texts(items, rng, "q")
    shapes = {k: cfg[k] for k in ARCH}
    grp = cfg["group"]
    names = [_sig(i) for i in range(n)]
    cache: Dict[str, Any] = {}

    def reference(quant: str = "f32"):
        """(the policy over reference embeddings of the descriptions,
        texts -> reference embeddings), at ``quant`` ("f32", or the
        "fp8" control)."""
        from bench.reference import encoder as ref
        ws = weights_seed(seed)
        if "w" not in cache:
            cache["w"] = ref.weights(shapes, ws)

        def embed(ts):
            return ref.embed(shapes, ws, [
                ref.tokenize(t, shapes["vocab_size"],
                             shapes["max_position_embeddings"], ws)
                for t in ts], quant, cache["w"])

        policy = RefPolicy(
            names=names, centroids=embed(desc), group=names,
            temperature=grp["temperature"],
            group_threshold=grp["threshold"], default=names[0],
            classifier={},
            rules=[RefRule(f"r{i:06d}", s, 1, 100 + i)
                   for i, s in enumerate(names)])
        return policy, embed

    return Built(
        dsl=_dsl(cfg, desc), embedder_dim=cfg["hidden_size"], embedder=None,
        texts=texts, make_texts=make_texts, reference=reference,
        backends=[], service=dict(validate=False, nprobe=cfg["nprobe"]),
        tie_margin=cfg["tie_margin"])
