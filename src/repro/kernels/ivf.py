"""Two-stage IVF routing lowerings over the bind-time slab bundle.

Stage 1 scores the query against the per-slab heads and keeps the
top-``nprobe`` coarse Voronoi regions; stage 2 gathers only those
slabs' quantized centroids, scores them, and runs the shared routing
tail (grouped softmax + thresholds + defaults + winners).  Both a pure
jnp lowering (`use_kernel=False`, the CPU/scale path) and a Pallas
lowering (coarse_topk + scalar-prefetch gather kernel from
kernels/voronoi) are provided; they are decision-identical, and with
``nprobe = n_slabs`` both reproduce the flat ``fused_route`` decisions
exactly (the hard parity oracle in tests/test_ivf.py).

Pruned (non-candidate) columns report raw = scores = 0 and cannot fire
— except through the per-group default fallback, which is re-applied at
full width so a pruned default column still catches a group where no
candidate fired, exactly as the flat kernel would when every member
score fell below θ.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import voronoi as _vor
from repro.kernels.voronoi import _HI, _NEG, _route_tail, unpack_int4


def _dequant_rows(rows: jnp.ndarray, d: int) -> jnp.ndarray:
    """(..., Ds) quantized store rows -> (..., d) f32 (uint8 rows are
    packed int4 nibble pairs; everything else is a plain cast)."""
    if rows.dtype == jnp.uint8:
        flat = rows.reshape(-1, rows.shape[-1])
        return unpack_int4(flat, d).reshape(rows.shape[:-1] + (d,))
    return rows.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=())
def flat_route(x, centroids, classifier_mask, col_scale, col_thr,
               grouped_mask, member, default_onehot, qscale=None):
    """Flat single-stage jnp lowering: full GEMM + shared routing tail.

    Same contract as ``fused_route`` (raw, scores, fired, win, wscore);
    this is the jnp-vs-jnp baseline the scale benchmark compares the
    two-stage path against, and it accepts every store precision
    including the packed-int4 uint8 format.
    """
    f32 = jnp.float32
    x = jnp.asarray(x, f32)
    n = centroids.shape[0]
    g = jnp.asarray(member).shape[0]
    m = (jnp.asarray(member, f32) if g
         else jnp.zeros((1, n), f32))
    dflt = (jnp.asarray(default_onehot, f32) if g
            else jnp.zeros((1, n), f32))
    deq = _dequant_rows(jnp.asarray(centroids), x.shape[1])
    sims = jax.lax.dot_general(x, deq, (((1,), (1,)), ((), ())),
                               precision=_HI, preferred_element_type=f32)
    if qscale is not None:
        sims = sims * jnp.asarray(qscale, f32).reshape(1, n)
    raw, scores, fired, win, wscore = _route_tail(
        sims,
        jnp.asarray(classifier_mask, f32).reshape(1, n),
        jnp.asarray(col_scale, f32).reshape(1, n),
        jnp.asarray(col_thr, f32).reshape(1, n),
        jnp.asarray(grouped_mask, f32).reshape(1, n),
        m, dflt)
    return raw, scores, fired, win[:, :g], wscore[:, :g]


def _scatter_to_columns(vals, cols, n, fill):
    """Scatter candidate-space (B, Kc) values to (B, N) column space.

    cols: (B, Kc) original column per slot, −1 for dead padding slots —
    those route to a dump column that is sliced off.  Every live column
    appears in at most one slab slot, so there are no collisions.
    """
    b = vals.shape[0]
    brow = jnp.arange(b)[:, None]
    colsafe = jnp.where(cols < 0, n, cols)
    base = jnp.full((b, n + 1), fill, vals.dtype)
    return base.at[brow, colsafe].set(vals)[:, :n]


def _canonicalize(raw, scores, fired, win, wscore, cand, member,
                  default):
    """Post-tail masking shared by both lowerings.

    Pruned columns carry zero raw/scores and cannot fire on their own
    (the ``_NEG`` sentinel keeps partially-pruned softmaxes exact, but
    a *fully* pruned group degenerates — with a small enough 1/τ its
    z-row stays finite and uniform — so fired is re-anchored to the
    candidate mask).  The per-group default fallback is then re-derived
    at full width: a pruned default column must still catch a group
    where no candidate fired.  A group whose every member was pruned
    reports the flat kernel's empty-group sentinel (win 0, wscore −1).
    """
    f32 = jnp.float32
    raw = jnp.where(cand, raw, 0.0)
    scores = jnp.where(cand, scores, 0.0)
    fired = fired & cand
    m = member.astype(f32)
    if m.shape[0]:
        group_any = jax.lax.dot_general(
            fired.astype(f32), m, (((1,), (1,)), ((), ())),
            preferred_element_type=f32) > 0.0                 # (B, G)
        fallback = jax.lax.dot_general(
            (~group_any).astype(f32), default.astype(f32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=f32) > 0.0                 # (B, N)
        fired = fired | fallback
    has_cand = jax.lax.dot_general(
        cand.astype(f32), m, (((1,), (1,)), ((), ())),
        preferred_element_type=f32) > 0.0                     # (B, G)
    win = jnp.where(has_cand, win, 0)
    wscore = jnp.where(has_cand, wscore, -1.0)
    return raw, scores, fired, win, wscore


@functools.partial(jax.jit, static_argnames=("nprobe",))
def _ivf_route_jnp(x, classifier_mask, col_scale, col_thr, grouped_mask,
                   member, default_onehot, heads, store, qscale_s,
                   slab_cols, *, nprobe: int):
    f32 = jnp.float32
    b, d = x.shape
    x = jnp.asarray(x, f32)
    n = classifier_mask.shape[-1]
    s = heads.shape[0]
    slab_k = store.shape[0] // s

    # stage 1: coarse Voronoi — top-nprobe slab heads per query
    hs = jax.lax.dot_general(x, jnp.asarray(heads, f32),
                             (((1,), (1,)), ((), ())), precision=_HI,
                             preferred_element_type=f32)      # (B, S)
    _, pidx = jax.lax.top_k(hs, nprobe)                       # (B, np)

    # stage 2: gather the probed slabs and score only their columns.
    # scan over probes keeps the working set at one (B, slab_k, D) slab
    # — the jnp analogue of the kernel's per-probe VMEM stream.
    store3 = store.reshape(s, slab_k, store.shape[1])
    qs3 = jnp.asarray(qscale_s, f32).reshape(s, slab_k)

    def _probe(_, pcol):
        slab = _dequant_rows(store3[pcol], d)                 # (B, k, D)
        sims = jnp.einsum("bkd,bd->bk", slab, x, precision=_HI,
                          preferred_element_type=f32)
        return None, sims * qs3[pcol]

    _, sims_c = jax.lax.scan(_probe, None, pidx.T)            # (np, B, k)
    sims_c = sims_c.transpose(1, 0, 2).reshape(b, nprobe * slab_k)

    # scatter candidate sims back to original column order; pruned
    # columns sit at _NEG so their softmax mass underflows to exactly 0
    cols3 = jnp.asarray(slab_cols, jnp.int32).reshape(s, slab_k)
    cols = cols3[pidx].reshape(b, nprobe * slab_k)            # (B, Kc)
    sims_full = _scatter_to_columns(sims_c, cols, n, jnp.float32(_NEG))
    cand = _scatter_to_columns(
        (cols >= 0), cols, n, jnp.asarray(False))

    raw, scores, fired, win, wscore = _route_tail(
        sims_full,
        jnp.asarray(classifier_mask, f32).reshape(1, n),
        jnp.asarray(col_scale, f32).reshape(1, n),
        jnp.asarray(col_thr, f32).reshape(1, n),
        jnp.asarray(grouped_mask, f32).reshape(1, n),
        jnp.asarray(member, f32),
        jnp.asarray(default_onehot, f32))
    return _canonicalize(raw, scores, fired, win, wscore, cand,
                         jnp.asarray(member, f32),
                         jnp.asarray(default_onehot, f32))


@functools.partial(jax.jit, static_argnames=("nprobe", "interpret"))
def _ivf_route_kernelized(x, classifier_mask, col_scale, col_thr,
                          grouped_mask, member, default_onehot, heads,
                          store, qscale_s, slab_cols, cls_s, scale_s,
                          thr_s, grp_s, member_s, default_s, colid_s, *,
                          nprobe: int, interpret: bool):
    f32 = jnp.float32
    b, d = x.shape
    x = jnp.asarray(x, f32)
    n = classifier_mask.shape[-1]
    s = heads.shape[0]
    slab_k = store.shape[0] // s

    _, pidx = _vor.coarse_topk(x, jnp.asarray(heads, f32), nprobe,
                               interpret=interpret)
    store3 = store.reshape(s, slab_k, store.shape[1])
    raw_c, scores_c, fired_c, win, wscore = _vor.ivf_route_candidates(
        x, pidx, store3, jnp.asarray(qscale_s, f32).reshape(1, s * slab_k),
        cls_s, scale_s, thr_s, grp_s, member_s, default_s, colid_s,
        interpret=interpret)

    cols3 = jnp.asarray(slab_cols, jnp.int32).reshape(s, slab_k)
    cols = cols3[pidx].reshape(b, nprobe * slab_k)
    raw = _scatter_to_columns(raw_c, cols, n, jnp.float32(0.0))
    scores = _scatter_to_columns(scores_c, cols, n, jnp.float32(0.0))
    fired = _scatter_to_columns(fired_c > 0.5, cols, n,
                                jnp.asarray(False))
    cand = _scatter_to_columns((cols >= 0), cols, n, jnp.asarray(False))
    return _canonicalize(raw, scores, fired, win, wscore, cand,
                         jnp.asarray(member, f32),
                         jnp.asarray(default_onehot, f32))


def ivf_route(x, classifier_mask, col_scale, col_thr, grouped_mask,
              member, default_onehot, ivf, *, nprobe: int,
              use_kernel: bool = False, interpret: bool = False):
    """Two-stage routing over a ``signals/ivf.build_ivf_tables`` bundle.

    x: (B, D) unit queries; the flat metadata operands are the same
    original-column-order arrays ``fused_route`` takes; ``ivf`` is the
    bind-time bundle (heads / quantized slab store / slab-space
    metadata).  ``nprobe`` is clamped to [1, n_slabs]; at n_slabs the
    candidate set is the whole table and the result is
    decision-identical to ``fused_route``.

    -> (raw (B,N), scores (B,N), fired (B,N) bool, win (B,G) int32,
    wscore (B,G)) — the flat contract, with pruned columns zeroed.
    """
    s = ivf["heads"].shape[0]
    nprobe = int(max(1, min(int(nprobe), s)))
    # groupless tables run with one all-zero padding group (the flat
    # wrapper's gp = max(g, 1) convention) and slice the winners back
    g = jnp.asarray(member).shape[0]
    n = jnp.asarray(classifier_mask).shape[-1]
    if g == 0:
        member = jnp.zeros((1, n), jnp.float32)
        default_onehot = jnp.zeros((1, n), jnp.float32)
    common = (x, classifier_mask, col_scale, col_thr, grouped_mask,
              member, default_onehot, ivf["heads"], ivf["store"],
              ivf["qscale_s"], ivf["slab_cols"])
    if not use_kernel:
        out = _ivf_route_jnp(*common, nprobe=nprobe)
    else:
        out = _ivf_route_kernelized(
            *common, ivf["cls_s"], ivf["scale_s"], ivf["thr_s"],
            ivf["grp_s"], ivf["member_s"], ivf["default_s"],
            ivf["colid_s"], nprobe=nprobe, interpret=interpret)
    if g == 0:
        raw, scores, fired, win, wscore = out
        return raw, scores, fired, win[:, :0], wscore[:, :0]
    return out
