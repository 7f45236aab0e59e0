"""Plain reference of the hashed character n-gram embedder.

The recipe the router's default embedder is documented to follow: the
lower-cased text wrapped in ``<`` ``>``, its character 3-, 4- and
5-grams and its ``w:``-prefixed words, each hashed with 32-bit FNV-1a
into one of ``n_buckets`` rows of a table of standard normals from
``jax.random.PRNGKey(seed)`` scaled by ``1/sqrt(dim)``; the mean of the
rows, L2-normalized.  Written here from that description, in float64,
one gram at a time.
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np


def grams(text: str) -> List[str]:
    t = f"<{text.lower()}>"
    out = [t[i:i + n] for n in (3, 4, 5) for i in range(max(0, len(t) - n + 1))]
    out += [f"w:{w}" for w in text.lower().split()]
    return out


def fnv1a(s: str) -> int:
    h = 2166136261
    for byte in s.encode("utf-8"):
        h = ((h ^ byte) * 16777619) & 0xFFFFFFFF
    return h


class RefHashEmbedder:
    def __init__(self, dim: int = 256, n_buckets: int = 1 << 15,
                 seed: int = 0):
        import jax
        import jax.numpy as jnp
        self.dim, self.n_buckets = dim, n_buckets
        table = jax.random.normal(jax.random.PRNGKey(seed),
                                  (n_buckets, dim), jnp.float32)
        self.table = np.asarray(jax.device_get(table), np.float64) \
            / np.sqrt(dim)
        self._memo = {}

    def _bucket(self, g: str) -> int:
        b = self._memo.get(g)
        if b is None:
            b = self._memo[g] = fnv1a(g) % self.n_buckets
        return b

    def embed(self, texts: Sequence[str]) -> np.ndarray:
        out = np.zeros((len(texts), self.dim), np.float64)
        for i, t in enumerate(texts):
            rows = [self._bucket(g) for g in grams(t)]
            if rows:
                out[i] = self.table[rows].mean(axis=0)
        return out / np.maximum(np.linalg.norm(out, axis=1, keepdims=True),
                                1e-8)
