"""Route-only cells whose router embeds every request on the device.

The deployment configures the router with an ``EncoderEmbedder``
(``repro.signals.embedder``) built from the configuration's encoder
numbers and the run's seed; the load loop and its window are
``bench/entries/route.py``'s.  Set-up also compiles and runs every
(rows, length) encoder program the mix's lengths can send, and routes
one call of every size up to ``admit_batch``, so nothing compiles in
the window.

The schedule keeps the generator's due times and its set of lengths,
but deals the lengths in blocks (``dealt``): each ``DEAL`` consecutive
requests carry one length from each of ``DEAL`` equal strata of the
set.  A stopped window above the knee routes a prefix of the schedule;
in the generator's random order that prefix's encoder work moved with
the seed (its long prompts cost up to 20 times a median one), and so
did ``routes_per_s``.  Dealt, every seed's prefix holds nearly the same
work.

``verify`` compares, on a sample stratified by length, the embeddings
the timed calls produced (recorded by wrapping the embedder) and their
route decisions with the plain reference (``bench/reference/encoder.py``
and ``bench/reference/router.py``); with ``run.control`` the fp8
reference takes the program's place.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import numpy as np

from bench import observe
from bench.builders import encoder_table
from bench.entries import route

# length bands of the sample, in tokens: (.., 128], (128, 256], ...,
# (4096, ..]; up to PER_BAND requests from each, LONG from the last
EDGES = (128, 256, 512, 1024, 2048, 4096)
PER_BAND = 40
LONG = 32
COUNTED = ("calls", "launches", "sequences", "tokens", "launched_tokens")
# requests a block of the dealt schedule holds, one from each stratum:
# the longest stratum then holds only the longest bucket's lengths
DEAL = 64


def encoder_config(cfg: Dict[str, Any]):
    from repro.configs.encoders import modernbert
    return modernbert(name=cfg["encoder"],
                      **{k: cfg[k] for k in encoder_table.ARCH})


def dealt(items: List[Any], seed: int) -> List[Any]:
    """``items`` with their lengths dealt in blocks of ``DEAL``: the
    sorted lengths cut into ``DEAL`` strata, each block one length from
    each stratum in a seeded order; due times stay where they were."""
    lengths = np.sort([it.prompt_bytes for it in items])
    rng = np.random.default_rng(seed ^ 0xDEA1)
    block = np.concatenate([rng.permutation(len(s))
                            for s in np.array_split(lengths, DEAL)])
    order = np.argsort(block + rng.random(len(lengths)), kind="stable")
    return [dataclasses.replace(it, prompt_bytes=int(n))
            for it, n in zip(items, lengths[order])]


def prepare(run) -> None:
    from repro.signals.embedder import EncoderEmbedder
    run.items = dealt(run.items, run.seed)
    run.built.texts = run.built.make_texts(
        run.items, np.random.default_rng(run.seed), "q")
    emb = EncoderEmbedder(encoder_config(run.cfg),
                          seed=encoder_table.weights_seed(run.seed))
    run.built.embedder = emb
    route.prepare(run)
    pb = run.mix["prompt_bytes"]
    run.encoder_programs = emb.warm(pb["min"], pb["max"])
    cap = run.mix["admit_batch"]
    warm = run.built.make_texts(run.warm_items(cap),
                                np.random.default_rng(run.seed ^ 0xE5C0),
                                "v")
    for k in range(1, cap + 1):        # the slices and pads of each size
        run.svc.route(warm[:k])
    run.embedded = {}

    def record(args, kwargs, out):
        for i, t in enumerate(args[0]):
            run.embedded[t] = (out, i)
    observe.wrap(emb, "embed", None, record)


def window(run, tracer) -> None:
    snap = lambda: run.svc.telemetry()["encoder"]   # noqa: E731
    before = snap()
    route.window(run, tracer)
    after = snap()
    run.stats["encoder"] = {k: after[k] - before[k] for k in COUNTED}


def sample(run) -> List[int]:
    """Routed requests, stratified by length band, seeded."""
    rows = sorted(run.recorder.decisions)
    band = np.searchsorted(EDGES, [run.items[i].prompt_bytes for i in rows])
    rng = np.random.default_rng(run.seed ^ 0xC0FFEE)
    take: List[int] = []
    for b in range(len(EDGES) + 1):
        members = [r for r, k in zip(rows, band) if k == b]
        want = LONG if b == len(EDGES) else PER_BAND
        if len(members) > want:
            members = rng.choice(members, size=want, replace=False).tolist()
        take += members
    return sorted(take)


def _program_embeddings(run, texts) -> np.ndarray:
    host: Dict[int, np.ndarray] = {}
    out = []
    for t in texts:
        arr, i = run.embedded[t]
        if id(arr) not in host:
            host[id(arr)] = np.asarray(arr, np.float64)
        out.append(host[id(arr)][i])
    return np.stack(out)


def verify(run) -> Dict[str, Any]:
    """Embedding and routing numbers of the sample (of the fp8 control
    in the program's place, with ``run.control``)."""
    from bench.reference.router import compare, decide
    b = run.built
    take = sample(run)
    texts = [b.texts[i] for i in take]
    got = [run.recorder.decisions[i] for i in take]
    prog = _program_embeddings(run, texts)
    band = np.searchsorted(EDGES, [run.items[i].prompt_bytes for i in take])
    run.embedded = None
    b.embedder = None
    run.release()
    policy, embed = b.reference()
    emb = embed(texts)
    ref = decide(policy, emb)
    if run.control:
        low_policy, low_embed = b.reference("fp8")
        prog = low_embed(texts)
        low = decide(low_policy, prog)
        routes, scores = low.route, low.score
    else:
        routes, scores = [g[0] for g in got], [g[1] for g in got]
    out = compare(routes, np.array(scores), ref, b.tie_margin)
    cos = np.sum(prog * emb, 1) / (np.linalg.norm(prog, axis=1)
                                  * np.linalg.norm(emb, axis=1))
    out["embed_cos_gap_max"] = float(np.max(1.0 - cos))
    out["embed_cos_gap_mean"] = float(np.mean(1.0 - cos))
    tie = ref.margin <= b.tie_margin
    out["tie_share"] = float(tie.mean())
    out["tie_share_band_max"] = float(max(
        tie[band == k].mean() for k in np.unique(band)))
    out["sampled"] = len(take)
    out["sampled_over_4096"] = int((band == len(EDGES)).sum())
    return out
