"""Seeded traffic: one general generator for every mix in ``bench/traffic``.

The recipe follows ``repro.workloads.generator`` (lognormal lengths,
Poisson arrivals, weighted tenants, unique texts) with one change that
keeps runs steady: every seed gets the same *set* of sizes and gaps, in
another order.  Lengths and inter-arrival gaps are the distribution's
quantiles at ``(i + 0.5) / n``, and the seed only permutes them and
draws the texts.  So the work in a window does not move with the seed;
what moves is which request carries which length, and when.

A mix file holds only data (see ``bench/traffic/*.json``):

    entry          "serve" (route then decode) or "route" (route only)
    rate_per_s     requests per second, each due at a fixed time
    prompt_bytes   {median, sigma, min, max} of a lognormal
    output_tokens  the same, for serve entries
    tenants        [{name, share}], a name the configuration's builder
                   knows how to write text for
    admit_batch    route entries: the most requests one route call takes
    close          route entries: "stop" ends the window at --seconds and
                   leaves what is still queued (see bench/entries/route.py)
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import statistics
from typing import Any, Dict, List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass(frozen=True)
class Item:
    """One request of a run, before the configuration gives it a text.

    Args:
        seq: position in the schedule.
        due_s: offset of its due time from the window start.
        tenant: tenant name from the mix.
        prompt_bytes: exact prompt length in bytes.
        output_tokens: decode budget (0 for route entries).
    """
    seq: int
    due_s: float
    tenant: str
    prompt_bytes: int
    output_tokens: int


def quantile_lengths(spec: Dict[str, Any], n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of a clamped lognormal, rounded."""
    q = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(float(p)) for p in q])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def quantile_gaps(rate: float, seconds: float, n: int) -> np.ndarray:
    """``n`` stratified exponential gaps scaled to fill ``seconds``."""
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q) / rate
    return g * (seconds / g.sum())


def tenant_list(tenants: List[Dict[str, Any]], n: int) -> List[str]:
    """Exactly ``round(share * n)`` of each tenant (largest remainders
    settle the rounding), in mix order."""
    shares = np.array([t["share"] for t in tenants], np.float64)
    raw = shares / shares.sum() * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    out: List[str] = []
    for t, c in zip(tenants, counts):
        out.extend([t["name"]] * int(c))
    return out


def schedule(mix: Dict[str, Any], seed: int, seconds: float) -> List[Item]:
    """The run's requests, from the mix and the seed:
    ``round(rate * seconds)`` requests due inside the window (open loop).
    """
    rng = np.random.default_rng(seed)
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    due = np.cumsum(rng.permutation(
        quantile_gaps(mix["rate_per_s"], seconds, n)))
    prompts = rng.permutation(quantile_lengths(mix["prompt_bytes"], n))
    if "output_tokens" in mix:
        outs = rng.permutation(quantile_lengths(mix["output_tokens"], n))
    else:
        outs = np.zeros(n, np.int64)
    names = tenant_list(mix["tenants"], n)
    tenants = [names[i] for i in rng.permutation(n)]
    return [Item(i, float(due[i]), tenants[i], int(prompts[i]), int(outs[i]))
            for i in range(n)]


def fingerprint(items: List[Item], texts: Optional[List[str]] = None) -> str:
    """Stable digest of a schedule (and its texts, when given)."""
    canon = [[it.seq, round(it.due_s, 9), it.tenant, it.prompt_bytes,
              it.output_tokens] for it in items]
    if texts is not None:
        canon.append(list(texts))
    return hashlib.sha1(json.dumps(canon).encode("utf-8")).hexdigest()


def fit_text(words: List[str], head: str, nbytes: int,
             rng: np.random.Generator) -> str:
    """``head`` followed by words drawn from ``words``, cut to exactly
    ``nbytes`` bytes (ASCII text, so bytes are prompt tokens)."""
    text = head
    while len(text) < nbytes:
        text += " " + words[int(rng.integers(len(words)))]
    return text[:nbytes]
