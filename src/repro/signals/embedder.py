"""Text embedders producing unit vectors on S^{d-1}.

Two backends, one interface:

* ``HashEmbedder`` — fastText-style hashed character n-grams projected
  through a fixed random matrix, mean-pooled, L2-normalized.  Deterministic
  and lexically meaningful without any training — the default for the
  validator's Monte-Carlo passes, TEST blocks, and examples.
* ``EncoderEmbedder`` — a bidirectional encoder (ModernBERT, from
  configs/encoders.py) over the models/ blocks, run on the device in
  length buckets; its embeddings stay on the device.

Both take ``embed(texts)`` and return (B, d) unit rows.
"""
from __future__ import annotations

import collections
import concurrent.futures
import functools
import zlib
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import encoder as encoder_mod
from repro.serving.spans import PhaseCounters


def _ngrams(text: str, lo: int = 3, hi: int = 5):
    t = f"<{text.lower()}>"
    for n in range(lo, hi + 1):
        for i in range(max(0, len(t) - n + 1)):
            yield t[i: i + n]
    for w in text.lower().split():
        yield f"w:{w}"


class HashEmbedder:
    """Hashed n-gram embedder, vectorized end to end.

    ``embed`` batches every text's n-gram bucket lookups into a single
    gather from the projection table (FNV-1a runs lockstep over a padded
    byte matrix instead of per-gram Python loops), and an LRU cache keyed
    on the exact text makes repeated prototype/query embeddings free —
    it was the dominant per-request cost in bench_router.py.
    """

    def __init__(self, dim: int = 256, n_buckets: int = 1 << 15,
                 seed: int = 0, cache_size: int = 8192):
        self.dim = dim
        self.n_buckets = n_buckets
        key = jax.random.PRNGKey(seed)
        self.table = np.asarray(
            jax.random.normal(key, (n_buckets, dim), jnp.float32)
        ) / np.sqrt(dim)
        self._cache_size = cache_size
        self._cache: "collections.OrderedDict[str, np.ndarray]" = \
            collections.OrderedDict()

    def _bucket(self, g: str) -> int:
        h = 2166136261
        for ch in g.encode("utf-8"):
            h = ((h ^ ch) * 16777619) & 0xFFFFFFFF
        return h % self.n_buckets

    def _buckets(self, grams: List[str]) -> np.ndarray:
        """Vectorized FNV-1a over a batch of n-grams (bit-identical to
        ``_bucket``): pad the utf-8 bytes to a (M, L) matrix and run the
        hash recurrence across all M grams at once, one step per byte
        position."""
        enc = [g.encode("utf-8") for g in grams]
        lens = np.fromiter((len(e) for e in enc), np.int64, len(enc))
        max_len = int(lens.max())
        flat = np.frombuffer(b"".join(enc), np.uint8)
        offs = np.zeros(len(enc), np.int64)
        np.cumsum(lens[:-1], out=offs[1:])
        rows = np.repeat(np.arange(len(enc)), lens)
        cols = np.arange(int(lens.sum())) - np.repeat(offs, lens)
        data = np.zeros((len(enc), max_len), np.uint64)
        data[rows, cols] = flat
        h = np.full(len(enc), 2166136261, np.uint64)
        for p in range(max_len):
            active = lens > p
            h = np.where(active, ((h ^ data[:, p]) * 16777619)
                         & 0xFFFFFFFF, h)
        return (h % self.n_buckets).astype(np.intp)

    def _embed_uncached(self, texts: Sequence[str]) -> np.ndarray:
        out = np.zeros((len(texts), self.dim), np.float32)
        grams: List[str] = []
        counts = np.zeros(len(texts), np.int64)
        for i, t in enumerate(texts):
            before = len(grams)
            grams.extend(_ngrams(t))
            counts[i] = len(grams) - before
        if grams:
            vecs = self.table[self._buckets(grams)]   # one batched gather
            off = 0
            for i, c in enumerate(counts):
                if c:
                    out[i] = vecs[off: off + c].mean(axis=0)
                off += c
        norm = np.linalg.norm(out, axis=1, keepdims=True)
        return out / np.maximum(norm, 1e-8)

    def embed(self, texts: Sequence[str]) -> np.ndarray:
        out = np.empty((len(texts), self.dim), np.float32)
        # duplicate texts in one batch coalesce onto a single miss row
        # (continuous-batching traffic repeats texts within a batch)
        miss_rows: "collections.OrderedDict[str, List[int]]" = \
            collections.OrderedDict()
        for i, t in enumerate(texts):
            v = self._cache.get(t)
            if v is None:
                miss_rows.setdefault(t, []).append(i)
            else:
                self._cache.move_to_end(t)
                out[i] = v
        if miss_rows:
            fresh = self._embed_uncached(list(miss_rows))
            for (t, rows), v in zip(miss_rows.items(), fresh):
                out[rows] = v
                # copy: caching the row view would pin the whole batch
                # array for as long as any one row survives in the LRU
                self._cache[t] = v.copy()
            while len(self._cache) > self._cache_size:
                self._cache.popitem(last=False)
        return out


# ---------------------------------------------------------------------------
# On-device encoder embedder
# ---------------------------------------------------------------------------

# most tokens one full launch holds, padding included
TOKEN_BUDGET = 32_768
# texts embedded together: their launches write into one (GROUP_ROWS, d)
# slab, which fixes every program's output shape
GROUP_ROWS = 16
MIN_BUCKET = 64
# distinct words whose ids the tokenizer keeps; past it the memo starts over
WORD_MEMO = 1 << 20


def _pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("slab",))
def _encode_core(params, tokens, lengths, slab, dest, *, cfg):
    """One launch of the encoder: (R, L) right-padded token rows ->
    their CLS embeddings, written into ``slab`` at rows ``dest``
    (out-of-range rows, the launch's padding, are dropped)."""
    emb = encoder_mod.encode(params, cfg, tokens, lengths)
    return slab.at[dest].set(emb, mode="drop")


class EncoderEmbedder:
    """Embeds texts with a bidirectional encoder on the device
    (``configs/encoders.py``); ``embed`` returns a (B, d) float32 device
    array, which the router hands to its route program as it is.

    The tokenizer stands in for the model's BPE, whose files are not in
    the repository: whitespace words, each the CRC-32 of its UTF-8
    bytes started from ``seed``, modulo the vocabulary less three; the
    top three ids are [CLS], [SEP] and [PAD].  A text is
    ``[CLS] words [SEP]``, cut to ``cfg.max_positions`` tokens.

    A call's texts go in groups of ``GROUP_ROWS``; a group's texts are
    bucketed by length (the smallest power of two at least as long, at
    least ``MIN_BUCKET``) and each bucket is launched as rows of that
    length (``plan``): one row, or a full launch of up to
    ``TOKEN_BUDGET`` tokens, never a padded row.  So there are two
    programs a bucket, and a text's numbers depend on its own length
    alone.
    """

    def __init__(self, cfg: ModelConfig, seed: int = 0):
        self.cfg = cfg
        self.dim = cfg.d_model
        self.seed = seed
        self.max_len = cfg.max_positions
        v = cfg.vocab_size
        self.cls, self.sep, self.pad = v - 3, v - 2, v - 1
        self._ids: Dict[str, int] = {}
        self.params = jax.jit(encoder_mod.init_encoder, static_argnums=1)(
            jax.random.PRNGKey(seed), cfg)
        self.phases = PhaseCounters()
        self._stats = {"calls": 0, "launches": 0, "sequences": 0,
                       "tokens": 0, "launched_tokens": 0, "max_len": 0}
        self._bucket_launches: Dict[int, int] = {}

    # ---- tokens and buckets --------------------------------------------
    def tokenize(self, text: str) -> List[int]:
        ids = self._ids
        if len(ids) > WORD_MEMO:
            ids.clear()
        out = [self.cls]
        for w in text.split()[: self.max_len - 2]:
            i = ids.get(w)
            if i is None:
                i = ids[w] = zlib.crc32(w.encode("utf-8"), self.seed) \
                    % self.cls
            out.append(i)
        out.append(self.sep)
        return out

    def bucket(self, n_tokens: int) -> int:
        return min(max(MIN_BUCKET, _pow2(n_tokens)), self.max_len)

    def full_rows(self, length: int) -> int:
        return min(GROUP_ROWS, max(1, TOKEN_BUDGET // length))

    def plan(self, lengths: Sequence[int]
             ) -> List[Tuple[int, int, List[int]]]:
        """Launches for one group: (rows, bucket length, the texts'
        indices), by length.  A bucket fills full launches of
        ``full_rows`` and launches each text left over alone, so no row
        is padding: a text's launched work depends on its own length,
        whatever its batch-mates.  A launch of one row of 128 tokens or
        more keeps the matrix units busy, and two programs a bucket keep
        the set to compile small."""
        by: Dict[int, List[int]] = {}
        for i, n in enumerate(lengths):
            by.setdefault(self.bucket(n), []).append(i)
        out = []
        for length in sorted(by):
            full, idx = self.full_rows(length), by[length]
            cut = len(idx) - len(idx) % full
            out += [(full, length, idx[j: j + full])
                    for j in range(0, cut, full)]
            out += [(1, length, [i]) for i in idx[cut:]]
        return out

    def programs(self, lo: int, hi: int) -> List[Tuple[int, int]]:
        """Every (rows, length) launch that texts of ``lo`` to ``hi``
        tokens can make."""
        out = []
        length = self.bucket(lo)
        while length <= self.bucket(hi):
            out += sorted({(1, length), (self.full_rows(length), length)})
            length *= 2
        return out

    def warm(self, lo: int, hi: int) -> int:
        """Compile and run every program ``programs`` names, compiling
        several at once; -> how many."""
        def run(shape):
            rows, length = shape
            return _encode_core(
                self.params, np.full((rows, length), self.pad, np.int32),
                np.full(rows, length, np.int32),
                jnp.zeros((GROUP_ROWS, self.dim), jnp.float32),
                np.arange(rows, dtype=np.int32), cfg=self.cfg)
        shapes = self.programs(lo, hi)
        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            jax.block_until_ready(list(pool.map(run, shapes)))
        return len(shapes)

    # ---- embedding -------------------------------------------------------
    def _group(self, toks: List[List[int]]):
        slab = jnp.zeros((GROUP_ROWS, self.dim), jnp.float32)
        st = self._stats
        for rows, length, idx in self.plan([len(t) for t in toks]):
            tokens = np.full((rows, length), self.pad, np.int32)
            lens = np.ones(rows, np.int32)
            dest = np.full(rows, GROUP_ROWS, np.int32)
            for r, i in enumerate(idx):
                t = toks[i]
                tokens[r, : len(t)] = t
                lens[r] = len(t)
                dest[r] = i
            slab = _encode_core(self.params, tokens, lens, slab, dest,
                                cfg=self.cfg)
            st["launches"] += 1
            st["launched_tokens"] += rows * length
            self._bucket_launches[length] = \
                self._bucket_launches.get(length, 0) + 1
        return slab

    def embed(self, texts: Sequence[str]):
        with self.phases.phase("router.encode", texts=len(texts)):
            toks = [self.tokenize(t) for t in texts]
            slabs = [self._group(toks[i: i + GROUP_ROWS])
                     for i in range(0, len(toks), GROUP_ROWS)]
            st = self._stats
            st["calls"] += 1
            st["sequences"] += len(toks)
            st["tokens"] += sum(len(t) for t in toks)
            st["max_len"] = max([st["max_len"]] + [len(t) for t in toks])
            if not slabs:
                return jnp.zeros((0, self.dim), jnp.float32)
            out = slabs[0] if len(slabs) == 1 else jnp.concatenate(slabs)
            return out[: len(texts)]

    def telemetry(self) -> Dict[str, Any]:
        """Cumulative counters: embed ``calls``, encoder ``launches``,
        ``sequences``, real ``tokens``, ``launched_tokens`` (padding
        included), ``pad_share`` of the launched tokens, the longest
        sequence ``max_len``, ``bucket_launches`` per bucket length, and
        the ``router.encode`` phase (n, seconds, over_100ms)."""
        st = dict(self._stats)
        st["pad_share"] = (1.0 - st["tokens"] / st["launched_tokens"]
                           if st["launched_tokens"] else 0.0)
        st["bucket_launches"] = dict(sorted(self._bucket_launches.items()))
        st["phases"] = self.phases.snapshot()
        return st
