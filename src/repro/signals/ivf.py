"""Bind-time layout for the two-stage IVF Voronoi router.

The paper's conflict-freedom result is a property of Voronoi
partitions: temperature-scaled softmax over a centroid set partitions
the unit sphere into regions where at most one signal can clear a
θ > 1/2 threshold.  That property *composes hierarchically* — a coarse
Voronoi over centroid clusters is itself a Voronoi partition of the
same sphere, so routing a query first to its top-``nprobe`` cluster
regions and then running the grouped softmax over only those clusters'
centroids cannot create a co-firing the flat table did not have
(restricting a softmax to a subset is still a softmax; see
docs/architecture.md).  With ``nprobe = n_slabs`` the candidate set is
the whole table and the two-stage router reproduces the flat kernel's
decisions exactly — the hard parity oracle the tests pin.

This module builds the bind-time artifacts, all in numpy:

* **spherical k-means** over the unit-norm centroid rows into
  ``n_clusters ≈ sqrt(n_routes)`` heads (greedy farthest-point
  seeding so binds are deterministic);
* a **slab layout**: clusters are split into chunks of at most
  ``2·N/K`` columns (so one runaway cluster cannot blow up the padded
  width), every chunk becomes one fixed-width *slab* of ``slab_k``
  columns (dead padding slots carry threshold 2.0 / no membership /
  column id −1), and each slab gets its own unit-norm head.  Fixed
  width means the fine-stage gather is a contiguous
  ``dynamic_slice`` at ``slab_id * slab_k`` — the CSR offsets
  degenerate to one stride;
* the **quantized slab store** via the engine's
  ``quantize_centroids`` (f32 / bf16 / int8, and the int4 *packed*
  format: two's-complement nibbles, two columns per byte), with the
  per-slot qscale carrying the same unit-norm threshold recalibration
  as the flat store — the same centroid row quantizes to the same
  values in both layouts, so decisions carry over bit-for-bit;
* slab-space views of the per-column metadata rows for the
  gather-then-score kernel, plus ``slab_cols`` mapping slab slots
  back to original probabilistic columns.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

# smallest chunk a cluster is split into
SLAB_ALIGN = 8
# slab widths round up to the TPU lane width: the fine-stage kernel
# stages probe p's slab at lane offset p·slab_k, and Mosaic stores at a
# dynamic lane offset only when it is a multiple of 128
SLAB_LANES = 128


# ---------------------------------------------------------------------------
# int4 packing: two's-complement nibbles, column j in the low nibble of
# byte j and column P+j in the high nibble, P = ceil(D/2) (odd D pads a
# zero column).  Unpacking is then a lane concatenation of the two
# halves, which the TPU kernels do per tile without an interleave.
# ---------------------------------------------------------------------------


def pack_int4(q: np.ndarray) -> np.ndarray:
    """(N, D) int8 values in [-8, 7] -> (N, ceil(D/2)) uint8 packed."""
    q = np.asarray(q, np.int8)
    n, d = q.shape
    if d % 2:
        q = np.concatenate([q, np.zeros((n, 1), np.int8)], axis=1)
    p = q.shape[1] // 2
    lo = q[:, :p].astype(np.uint8) & 0xF
    hi = q[:, p:].astype(np.uint8) & 0xF
    return (lo | (hi << 4)).astype(np.uint8)


def unpack_int4(packed: np.ndarray, d: int) -> np.ndarray:
    """(N, P) uint8 packed -> (N, d) f32 values in [-8, 7]."""
    p = np.asarray(packed, np.uint8)
    lo = (p & 0xF).astype(np.int32)
    lo = lo - np.where(lo > 7, 16, 0)
    hi = (p >> 4).astype(np.int32)
    hi = hi - np.where(hi > 7, 16, 0)
    return np.concatenate([lo, hi], axis=1)[:, :d].astype(np.float32)


# ---------------------------------------------------------------------------
# clustering + slab layout
# ---------------------------------------------------------------------------


def spherical_kmeans(c: np.ndarray, k: int, *, iters: int = 8,
                     seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Deterministic spherical k-means over unit rows.

    c: (N, D) unit-norm f32 rows -> (heads (K, D) unit f32,
    assign (N,) int32 with assign[i] = argmax_k heads[k]·c[i]).

    Seeding is greedy farthest-point ("sphere cover"): start from row
    0, repeatedly pick the row worst-covered by the chosen heads — no
    RNG, so binds of the same table are bit-identical across
    processes.  Lloyd iterations assign by max cosine and renormalize
    cluster means; an emptied cluster is re-seeded with the overall
    worst-covered point.  ``seed`` only rotates the starting row (kept
    for experiments; the default 0 keeps determinism trivial).
    """
    c = np.asarray(c, np.float32)
    n, d = c.shape
    k = int(max(1, min(k, n)))
    heads = np.zeros((k, d), np.float32)
    heads[0] = c[seed % n]
    if k > 1:
        best = c @ heads[0]
        for i in range(1, k):
            nxt = int(np.argmin(best))
            heads[i] = c[nxt]
            best = np.maximum(best, c @ heads[i])
    assign = np.zeros(n, np.int32)
    for _ in range(max(1, int(iters))):
        sims = c @ heads.T                                    # (N, K)
        assign = np.argmax(sims, axis=1).astype(np.int32)
        sums = np.zeros((k, d), np.float32)
        np.add.at(sums, assign, c)
        counts = np.bincount(assign, minlength=k)
        worst = int(np.argmin(sims.max(axis=1)))
        for g in range(k):
            if counts[g] == 0:
                heads[g] = c[worst]
                assign[worst] = g
                continue
            norm = float(np.linalg.norm(sums[g]))
            heads[g] = sums[g] / max(norm, 1e-8)
    return heads, assign


def build_slab_layout(assign: np.ndarray, k: int
                      ) -> Tuple[List[np.ndarray], int]:
    """Split clusters into bounded chunks and fix the common slab width.

    -> (chunks: per-slab original-column index arrays, slab_k).  Chunks
    cap at ``max(SLAB_ALIGN, ceil(2N/K))`` columns so an adversarially
    imbalanced clustering cannot inflate the padded slab width — an
    oversized cluster simply becomes several slabs, each with its own
    head, which is still a Voronoi partition of the sphere.
    """
    assign = np.asarray(assign)
    n = assign.shape[0]
    cap = max(SLAB_ALIGN, int(math.ceil(2.0 * n / max(k, 1))))
    chunks: List[np.ndarray] = []
    for g in range(k):
        cols = np.where(assign == g)[0].astype(np.int32)
        for lo in range(0, cols.size, cap):
            chunks.append(cols[lo: lo + cap])
    if not chunks:
        chunks = [np.zeros(0, np.int32)]
    width = max(int(ch.size) for ch in chunks)
    slab_k = SLAB_LANES * max(1, math.ceil(width / SLAB_LANES))
    return chunks, slab_k


def default_nprobe(n_slabs: int) -> int:
    """Default stage-1 fan-out: ~sqrt(K) + slack, clamped to [1, K].
    Tuned against the recall@1 ≥ 0.99 gate in tests/test_ivf.py."""
    return max(1, min(int(n_slabs), int(math.ceil(math.sqrt(n_slabs))) + 2))


# ---------------------------------------------------------------------------
# the bind-time bundle
# ---------------------------------------------------------------------------


def build_ivf_tables(centroids: np.ndarray, classifier_mask: np.ndarray,
                     col_scale: np.ndarray, col_thr: np.ndarray,
                     grouped_mask: np.ndarray, member_full: np.ndarray,
                     default_full: np.ndarray, *, precision: str = "f32",
                     n_clusters: int | None = None, iters: int = 8,
                     seed: int = 0) -> Dict[str, np.ndarray]:
    """Cluster + slab-pack a flat routing table into the IVF bundle.

    Inputs are the flat ``fused_route`` operands (original column
    order); the result is a dict of numpy arrays consumed by
    ``kernels/ivf.ivf_route``:

    * ``heads``     (S, D) f32 — unit head per slab (S = n_slabs)
    * ``store``     (S·slab_k, D) quantized slab centroids (uint8
      packed pairs of int4 nibbles when ``precision == "int4"``)
    * ``qscale_s``  (1, S·slab_k) dequantization scale per slab slot
    * ``slab_cols`` (S·slab_k,) int32 original column per slot, −1 dead
    * ``cls_s`` / ``scale_s`` / ``thr_s`` / ``grp_s`` (1, S·slab_k)
      slab-space metadata rows (dead slots: threshold 2.0)
    * ``member_s`` / ``default_s`` (max(G,1), S·slab_k)
    * ``colid_s``   (1, S·slab_k) f32 copy of slab_cols for in-kernel
      winner globalization (column ids are exact in f32 below 2²⁴)

    ``n_slabs`` and ``slab_k`` are recoverable from shapes:
    ``heads.shape[0]`` and ``store.shape[0] // heads.shape[0]``.
    """
    from repro.signals.engine import quantize_centroids
    c = np.asarray(centroids, np.float32)
    n, d = c.shape
    if n_clusters is None:
        n_clusters = max(1, int(round(math.sqrt(max(n, 1)))))
    heads0, assign = spherical_kmeans(c, n_clusters, iters=iters,
                                      seed=seed)
    chunks, slab_k = build_slab_layout(assign, heads0.shape[0])
    s = len(chunks)
    ns = s * slab_k
    slab_cols = np.full(ns, -1, np.int32)
    heads = np.zeros((s, d), np.float32)
    slab_c = np.zeros((ns, d), np.float32)
    for i, cols in enumerate(chunks):
        lo = i * slab_k
        slab_cols[lo: lo + cols.size] = cols
        slab_c[lo: lo + cols.size] = c[cols]
        if cols.size:
            m = c[cols].mean(axis=0)
            heads[i] = m / max(float(np.linalg.norm(m)), 1e-8)
    store, qscale = quantize_centroids(slab_c, precision)
    live = slab_cols >= 0

    def row(v: np.ndarray, fill: float) -> np.ndarray:
        out = np.full((1, ns), fill, np.float32)
        out[0, live] = np.asarray(v, np.float32)[slab_cols[live]]
        return out

    g = member_full.shape[0]
    gp = max(g, 1)
    member_s = np.zeros((gp, ns), np.float32)
    default_s = np.zeros((gp, ns), np.float32)
    if g:
        member_s[:g, live] = np.asarray(
            member_full, np.float32)[:, slab_cols[live]]
        default_s[:g, live] = np.asarray(
            default_full, np.float32)[:, slab_cols[live]]
    return {
        "heads": heads,
        "store": store,
        "qscale_s": np.asarray(qscale, np.float32).reshape(1, ns),
        "slab_cols": slab_cols,
        "cls_s": row(np.asarray(classifier_mask, np.float32), 0.0),
        "scale_s": row(col_scale, 0.0),
        "thr_s": row(col_thr, 2.0),
        "grp_s": row(grouped_mask, 0.0),
        "member_s": member_s,
        "default_s": default_s,
        "colid_s": slab_cols.astype(np.float32).reshape(1, ns),
    }
