"""Fused Voronoi-normalization Pallas kernels (the paper's §4 runtime
mechanism as TPU kernels).

Three entry points:

* ``voronoi_scores`` — softmax(X @ Cᵀ / τ) for one group's centroid
  matrix C (K, D) against unit queries X (B, D); similarity matmul and
  the numerically-stable softmax fuse in one kernel.
* ``voronoi_normalize_sims`` — softmax(S / τ) over precomputed
  similarities for a single group.
* ``fused_route`` — the whole signal layer in one launch: the
  (B, D)·(D, N) similarity GEMM against the stacked centroid matrix
  (centroids resident in VMEM, tiled over N through a fori_loop that
  writes each MXU-sized tile into a VMEM scratch, so centroid counts
  beyond one tile never issue one huge dot), classifier calibration,
  the segment-masked grouped softmax, per-column thresholds with
  per-group default fallback, and per-group winner indices + scores —
  five outputs, one kernel.
* ``fused_route_dtiled`` — the same contract for embedder dims too
  large to keep the whole (N, D) centroid matrix VMEM-resident: the
  grid gains a second (D-chunk) dimension, each step streams one
  (N, block_d) centroid slab and one (bb, block_d) query slab through
  the MXU and accumulates partial similarities into a VMEM scratch
  accumulator; the last chunk applies the per-column dequantization
  scale and runs the identical post-GEMM tail (calibration, grouped
  softmax, thresholds/defaults, winners).  Resident VMEM is
  O(N·block_d + bb·N) instead of O(N·D).

Both fused variants accept a per-column ``qscale`` vector applied to
the accumulated similarities — the hook for bf16/int8 centroid stores:
quantized centroids dequantize to unit norm through ``qscale`` while
the GEMM accumulates in f32 (see signals/engine quantization).
* ``grouped_voronoi`` — the *whole policy's* groups in one launch:
  given the stacked similarity matrix S (B, N) for every probabilistic
  signal, a per-column 1/τ vector, and a (G, N) one-hot membership
  partition, it computes the segment-masked softmax of every group
  simultaneously.  Contract: membership is a partition (each column in
  exactly one group row, groups may be uneven/singleton); per-column
  scales are constant within a group; output column j is the softmax of
  group(j) restricted to its member columns.  Per-group maxima come
  from a loop over the G group rows; broadcasts and denominators are
  one-hot matmuls on the MXU.  This replaces one kernel launch per
  SIGNAL_GROUP with exactly one launch per batch.

All kernels tile queries over VMEM blocks of ``block_b`` rows
(MXU-aligned 128) and keep the small operands (centroids, scales,
membership) resident in VMEM across the grid.  Validated on CPU with
``interpret=True`` against kernels/ref.py, and compiled for a described
v5e chip by tests/test_chip_compile.py.

Mosaic (the TPU kernel compiler) lowers no ``dynamic_slice`` or
``dynamic_update_slice`` on values.  So the group loop picks row g by a
one-hot contraction and writes column g by a lane-iota select
(``_per_group``), and the N-tile loop writes each tile into a VMEM
scratch at a ``pl.multiple_of``-aligned offset.  Every one-hot and
similarity contraction runs at ``HIGHEST`` precision: on the TPU the
default f32 matmul rounds its operands to bf16, which would move scores
near a threshold differently in each lowering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pad_rows(x: jnp.ndarray, block_b: int):
    """Pad x's rows to a multiple of the block size so the grid really
    tiles: -> (padded x, block rows bb, #blocks).  Batches smaller than
    ``block_b`` become a single bb=B block; larger batches are padded up
    to a block_b multiple instead of degrading to one whole-batch block."""
    b = x.shape[0]
    bb = max(1, min(block_b, b))
    pad = (-b) % bb
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x, bb, x.shape[0] // bb


def _voronoi_kernel(x_ref, c_ref, inv_tau_ref, o_ref):
    x = x_ref[...]                                   # (bb, D)
    c = c_ref[...]                                   # (K, D)
    sims = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (bb, K)
    z = sims * inv_tau_ref[0]
    z = z - jnp.max(z, axis=-1, keepdims=True)
    e = jnp.exp(z)
    o_ref[...] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def voronoi_scores(x: jnp.ndarray, centroids: jnp.ndarray,
                   temperature: float | jnp.ndarray, *,
                   block_b: int = 128, interpret: bool = False
                   ) -> jnp.ndarray:
    """x: (B, D); centroids: (K, D) -> (B, K) Voronoi scores."""
    b, d = x.shape
    k = centroids.shape[0]
    x, bb, nb = _pad_rows(x, block_b)
    inv_tau = jnp.asarray([1.0 / temperature], jnp.float32)
    out = pl.pallas_call(
        _voronoi_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bb, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),   # resident centroids
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bb, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], k), jnp.float32),
        interpret=interpret,
    )(x, centroids, inv_tau)
    return out[:b]


_NEG = -3e38                   # finite -inf stand-in: 0 * _NEG == 0, not nan
_HI = jax.lax.Precision.HIGHEST


def _dot(a, b, dims):
    """f32 contraction at full precision (see the module docstring)."""
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _per_group(m: jnp.ndarray, fn, init):
    """Loop over the G membership rows of ``m`` (G, N): ``fn(row)``
    maps row g (1, N) to (bb, 1) results (one or a tuple), which land
    in column g of the matching (bb, G) carries in ``init``.  Row g is
    picked by a one-hot contraction and column g written by a lane-iota
    select — Mosaic lowers neither a dynamic row slice nor a dynamic
    column update of a value — and the loop stays a loop, so the VMEM
    of the (bb, N) temporaries does not grow with G."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, m.shape[0]), 1)

    def body(g, acc):
        hit = lane == g
        row = _dot(hit.astype(jnp.float32), m, ((1,), (0,)))  # (1, N)
        return jax.tree.map(lambda a, v: jnp.where(hit, v, a), acc,
                            fn(row))

    return jax.lax.fori_loop(0, m.shape[0], body, init)


def unpack_int4(packed: jnp.ndarray, d: int) -> jnp.ndarray:
    """(N, P) uint8 packed int4 -> (N, d) f32 in [-8, 7].

    Column j lives in the low nibble of byte j, column P+j in the high
    nibble, two's-complement (signals/ivf.pack_int4 is the inverse).
    Nibble ops are VPU-elementwise and the halves join by one lane
    concatenation, so in-kernel unpack adds no MXU work — the store
    stays half an int8 in VMEM/HBM.
    """
    p = packed.astype(jnp.int32)
    lo = p & 0xF
    lo = lo - jnp.where(lo > 7, 16, 0)
    hi = (p >> 4) & 0xF
    hi = hi - jnp.where(hi > 7, 16, 0)
    return jnp.concatenate([lo, hi], axis=1)[:, :d].astype(jnp.float32)


def _dequant_tile(cj: jnp.ndarray, unpack_d: int) -> jnp.ndarray:
    """Per-tile dequantization of a centroid-store slice: int4 unpack
    when ``unpack_d`` is set (the packed column count halves), plain
    f32 cast otherwise.  Casting per tile — not the whole resident
    store — is what keeps a quantized store's VMEM cost at its own
    dtype plus ONE (block, D) f32 tile (kernels/ops accounting)."""
    if unpack_d:
        return unpack_int4(cj, unpack_d)
    return cj.astype(jnp.float32)


def _tiled_sims(x, c_ref, acc_ref, block_n: int, unpack_d: int):
    """x (bb, D) · dequant(store (Npad, Ds))ᵀ -> (bb, Npad) f32, one
    (block_n, Ds) tile at a time: each fori_loop step slices the store
    in its OWN dtype, dequantizes that tile only (a bf16/int8/int4
    store never materializes as f32), and writes the tile's sims into
    the VMEM scratch ``acc_ref`` at a block_n-aligned lane offset.  A
    single tile skips the scratch."""
    n_tiles = c_ref.shape[0] // block_n
    if n_tiles == 1:
        return _dot(x, _dequant_tile(c_ref[...], unpack_d), ((1,), (1,)))

    def _tile(j, carry):
        off = pl.multiple_of(j * block_n, block_n)
        cj = _dequant_tile(c_ref[pl.ds(off, block_n), :], unpack_d)
        acc_ref[:, pl.ds(off, block_n)] = _dot(x, cj, ((1,), (1,)))
        return carry

    jax.lax.fori_loop(0, n_tiles, _tile, 0)
    return acc_ref[...]


def _softmax_by_group(z: jnp.ndarray, m: jnp.ndarray, *,
                      reduce_max=None, reduce_sum=None) -> jnp.ndarray:
    """Segment-masked, numerically stable softmax over every group at
    once — the shared value-level body of the grouped kernels.

    z: (bb, N) scaled logits; m: (G, N) one-hot group membership (at
    most one group per column; columns in no group get a harmless
    guarded value the caller must mask out).  -> (bb, N) where member
    column j holds the softmax of group(j) restricted to its columns.

    The per-group max is a loop of masked row reductions over the G
    groups; the max/denominator broadcast back to columns and the
    per-group sum both ride the MXU as one-hot matmuls, so the whole
    batch needs exactly one kernel launch regardless of group count.

    ``reduce_max``/``reduce_sum`` are the cross-device collective hooks
    for the shard_map lowering (signals/engine): when N is sharded over
    a mesh axis, the per-group maxima and denominators reduce across
    shards (pmax/psum) between the local reductions and the broadcast
    back to columns.  None (the kernel case) means single-shard.
    """
    covered = jnp.sum(m, axis=0, keepdims=True) > 0.0         # (1, N)
    gmax = _per_group(m, lambda row: jnp.max(                 # (bb, G)
        jnp.where(row > 0.0, z, _NEG), axis=-1, keepdims=True),
        jnp.full((z.shape[0], m.shape[0]), _NEG, jnp.float32))
    if reduce_max is not None:
        gmax = reduce_max(gmax)
    col_max = _dot(gmax, m, ((1,), (0,)))                     # (bb, N)
    e = jnp.exp(jnp.where(covered, z - col_max, 0.0))         # ≤ 1 covered
    gsum = _dot(e, m, ((1,), (1,)))                           # (bb, G)
    if reduce_sum is not None:
        gsum = reduce_sum(gsum)
    denom = _dot(gsum, m, ((1,), (0,)))                       # (bb, N) ≥ 1
    return e / jnp.maximum(denom, 1e-30)     # guard: uncovered denom == 0


def _grouped_voronoi_kernel(s_ref, scale_ref, member_ref, o_ref):
    """One launch for the whole partition: see ``_softmax_by_group``.

    s_ref:      (bb, N) raw similarities for this batch block
    scale_ref:  (1, N)  per-column 1/temperature (constant within a group)
    member_ref: (G, N)  one-hot group membership — a partition: every
                column belongs to exactly one group row
    o_ref:      (bb, N) per-column softmax over the column's group
    """
    z = s_ref[...].astype(jnp.float32) * scale_ref[...]       # (bb, N)
    m = member_ref[...].astype(jnp.float32)                   # (G, N)
    o_ref[...] = _softmax_by_group(z, m).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def grouped_voronoi(sims: jnp.ndarray, inv_tau: jnp.ndarray,
                    member: jnp.ndarray, *,
                    block_b: int = 128, interpret: bool = False
                    ) -> jnp.ndarray:
    """sims: (B, N); inv_tau: (N,); member: (G, N) one-hot partition
    -> (B, N) grouped Voronoi scores in one pallas_call."""
    b, n = sims.shape
    g = member.shape[0]
    sims, bb, nb = _pad_rows(sims, block_b)
    scale = jnp.asarray(inv_tau, jnp.float32).reshape(1, n)
    memberf = jnp.asarray(member, jnp.float32)
    out = pl.pallas_call(
        _grouped_voronoi_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bb, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),   # resident scales
            pl.BlockSpec((g, n), lambda i: (0, 0)),   # resident membership
        ],
        out_specs=pl.BlockSpec((bb, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((sims.shape[0], n), jnp.float32),
        interpret=interpret,
    )(sims, scale, memberf)
    return out[:b]


def _route_tail(sims, cls, scale, thr, grouped_row, member, default, *,
                reduce_max=None, reduce_sum=None, reduce_min=None,
                col_offset=0):
    """Shared post-GEMM half of the fused routing lowerings: classifier
    calibration, grouped softmax, thresholds + default fallback, and
    per-group winners, all on values already resident.

    sims: (bb, Np) accumulated (and dequantized) similarities; the
    remaining operands are the (1, Np)/(G, Np) column-metadata values
    described on ``_fused_route_kernel``.
    -> (raw, scores, fired_bool, win, wscore).

    The keyword hooks make this the ONE copy of the routing semantics
    shared by the Pallas kernels (hooks None: single shard) and the
    shard_map lowering in signals/engine (N sharded over a mesh axis):
    ``reduce_max``/``reduce_sum`` cross-shard the softmax maxima,
    denominators and fired-any reductions; ``col_offset`` globalizes
    the local argmax column index; the winner is then the smallest
    global index attaining the reduce_max'd best score — the same
    first-occurrence argmax the single-shard path computes directly.
    """
    f32 = jnp.float32
    grouped = grouped_row > 0.0                               # (1, Np)
    raw = jnp.where(cls > 0.0, (sims + 1.0) * 0.5, sims)
    z = sims * scale
    m = member.astype(f32)                                    # (G, Np)
    scores = jnp.where(
        grouped,
        _softmax_by_group(z, m, reduce_max=reduce_max,
                          reduce_sum=reduce_sum),
        raw)

    # grouped columns threshold strictly at the group θ; ungrouped use
    # the signal's own inclusive threshold (engine semantics, Def 1);
    # a select between two bool vectors does not lower in Mosaic
    fired = (grouped & (scores > thr)) | (~grouped & (raw >= thr))
    group_any = _dot(fired.astype(f32), m, ((1,), (1,)))     # (bb, G)
    if reduce_sum is not None:
        group_any = reduce_sum(group_any)
    group_any = group_any > 0.0
    fallback = _dot((~group_any).astype(f32), default,        # (bb, Np)
                    ((1,), (0,))) > 0.0
    fired = fired | fallback

    def _winner(row):
        sg = jnp.where(row > 0.0, scores, -1.0)               # scores ≥ 0
        return (jnp.argmax(sg, axis=-1, keepdims=True).astype(jnp.int32),
                jnp.max(sg, axis=-1, keepdims=True))

    bb, n_groups = scores.shape[0], m.shape[0]
    win, wscore = _per_group(m, _winner, (
        jnp.zeros((bb, n_groups), jnp.int32),
        jnp.full((bb, n_groups), -1.0, f32)))
    win = win + jnp.asarray(col_offset, jnp.int32)            # (bb, G)
    if reduce_max is not None:
        best = reduce_max(wscore)                             # (bb, G)
        win = reduce_min(jnp.where(wscore >= best, win,
                                   jnp.int32(1 << 30)))       # first global
        win = jnp.where(best < 0.0, 0, win)                   # empty group
        wscore = best
    return raw, scores, fired, win, wscore


def _fused_route_kernel(x_ref, c_ref, qscale_ref, cls_ref, scale_ref,
                        thr_ref, grouped_ref, member_ref, default_ref,
                        raw_ref, scores_ref, fired_ref, win_ref,
                        wscore_ref, acc_ref, *, block_n: int,
                        unpack_d: int = 0):
    """The whole signal layer for one query block, single launch.

    x_ref:       (bb, D)   unit query embeddings
    c_ref:       (Np, D)   stacked centroid matrix, VMEM-resident
                 (f32, bf16 or int8 — dequantized through qscale)
    qscale_ref:  (1, Np)   per-column dequantization scale applied to
                 the accumulated similarities (1.0 for f32 centroids)
    cls_ref:     (1, Np)   1.0 where the column is a classifier signal
                 (raw = (sim+1)/2 calibration), 0.0 for geometric
    scale_ref:   (1, Np)   1/temperature for grouped columns, 1.0 else
    thr_ref:     (1, Np)   group θ for grouped columns, the signal's own
                 threshold for ungrouped ones (padded columns: > 1)
    grouped_ref: (1, Np)   1.0 where the column belongs to a SIGNAL_GROUP
    member_ref:  (G, Np)   one-hot partition of the grouped columns
    default_ref: (G, Np)   one-hot default member per group (may be zero)

    Emits raw calibrated scores, grouped-normalized scores, fired mask
    (thresholds + default fallback), and the per-group winner column +
    winning score.  The similarity GEMM runs tiled over N
    (``_tiled_sims``, scratch ``acc_ref`` (bb, Np)), so N beyond one MXU
    tile streams instead of issuing one huge dot.
    """
    x = x_ref[...].astype(jnp.float32)                        # (bb, D)
    sims = _tiled_sims(x, c_ref, acc_ref, block_n, unpack_d)
    sims = sims * qscale_ref[...]

    raw, scores, fired, win, wscore = _route_tail(
        sims, cls_ref[...], scale_ref[...], thr_ref[...],
        grouped_ref[...], member_ref[...], default_ref[...])
    raw_ref[...] = raw
    scores_ref[...] = scores
    fired_ref[...] = fired.astype(jnp.float32)
    win_ref[...] = win
    wscore_ref[...] = wscore


def _fused_route_dtiled_kernel(x_ref, c_ref, qscale_ref, cls_ref,
                               scale_ref, thr_ref, grouped_ref,
                               member_ref, default_ref,
                               raw_ref, scores_ref, fired_ref, win_ref,
                               wscore_ref, acc_ref, *, n_dtiles: int):
    """D-tiled twin of ``_fused_route_kernel``: grid (batch, D-chunk).

    Each (i, j) step sees one (bb, block_d) query slab and one
    (N, block_d) centroid slab — only a D-slice of the centroid matrix
    is ever VMEM-resident — and accumulates the partial similarity
    contribution into the persistent (bb, N) f32 scratch ``acc_ref``.
    The last chunk (j == n_dtiles - 1) applies the per-column
    dequantization scale and the shared post-GEMM tail, then writes all
    five outputs for the batch block.  D-chunks are the innermost grid
    dimension, so the scratch accumulator carries across the chunks of
    one batch block and re-zeroes when the next block starts.
    """
    f32 = jnp.float32
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(f32)                                # (bb, bd)
    c = c_ref[...].astype(f32)                                # (N, bd)
    acc_ref[...] += _dot(x, c, ((1,), (1,)))

    @pl.when(j == n_dtiles - 1)
    def _finish():
        sims = acc_ref[...] * qscale_ref[...]
        raw, scores, fired, win, wscore = _route_tail(
            sims, cls_ref[...], scale_ref[...], thr_ref[...],
            grouped_ref[...], member_ref[...], default_ref[...])
        raw_ref[...] = raw
        scores_ref[...] = scores
        fired_ref[...] = fired.astype(jnp.float32)
        win_ref[...] = win
        wscore_ref[...] = wscore


def _centroid_store_dtype(centroids) -> jnp.dtype:
    """Quantized centroid stores keep their dtype in VMEM (that's the
    memory-traffic win); anything else is promoted to f32.  uint8 is
    the packed-int4 container (two nibbles per byte)."""
    dt = jnp.asarray(centroids).dtype
    return dt if dt in (jnp.bfloat16, jnp.int8, jnp.uint8) else jnp.float32


@functools.partial(jax.jit, static_argnames=("block_b", "block_n",
                                             "interpret"))
def fused_route(x: jnp.ndarray, centroids: jnp.ndarray,
                classifier_mask: jnp.ndarray, col_scale: jnp.ndarray,
                col_thr: jnp.ndarray, grouped_mask: jnp.ndarray,
                member: jnp.ndarray, default_onehot: jnp.ndarray, *,
                qscale: jnp.ndarray | None = None,
                block_b: int = 128, block_n: int = 128,
                interpret: bool = False):
    """Fully-fused signal layer: one launch from embeddings to fired
    activations and per-group winners.

    x: (B, D) unit queries; centroids: (N, D) stacked centroid matrix
    (f32, or a bf16/int8 quantized store dequantized through
    ``qscale``); classifier_mask/col_scale/col_thr/grouped_mask: (N,)
    per-column metadata; member/default_onehot: (G, N) one-hot
    partition + default; qscale: optional (N,) per-column scale on the
    accumulated similarities (default all-ones).
    -> (raw (B,N) f32, scores (B,N) f32, fired (B,N) bool,
        win (B,G) int32 global column index, wscore (B,G) f32).
    """
    b, d = x.shape
    n = centroids.shape[0]
    g = member.shape[0]
    f32 = jnp.float32
    x, bb, nb = _pad_rows(x, block_b)
    bn = max(1, min(block_n, n))
    pad_n = (-n) % bn
    npad = n + pad_n
    gp = max(g, 1)

    cdt = _centroid_store_dtype(centroids)
    packed = jnp.asarray(centroids).dtype == jnp.uint8
    dstore = centroids.shape[1]          # ceil(d/2) for packed int4
    cmat = jnp.zeros((npad, dstore), cdt).at[:n].set(
        jnp.asarray(centroids, cdt))
    row = lambda v, fill: jnp.full((1, npad), fill, f32).at[0, :n].set(
        jnp.asarray(v, f32))
    qs = row(jnp.ones(n, f32) if qscale is None else qscale, 1.0)
    cls = row(classifier_mask, 0.0)
    scale = row(col_scale, 0.0)
    thr = row(col_thr, 2.0)            # padded columns can never fire
    grp = row(grouped_mask, 0.0)
    memberp = jnp.zeros((gp, npad), f32).at[:g, :n].set(
        jnp.asarray(member, f32))
    defaultp = jnp.zeros((gp, npad), f32).at[:g, :n].set(
        jnp.asarray(default_onehot, f32))

    raw, scores, fired, win, wscore = pl.pallas_call(
        functools.partial(_fused_route_kernel, block_n=bn,
                          unpack_d=d if packed else 0),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bb, d), lambda i: (i, 0)),
            pl.BlockSpec((npad, dstore), lambda i: (0, 0)),  # resident store
            pl.BlockSpec((1, npad), lambda i: (0, 0)),
            pl.BlockSpec((1, npad), lambda i: (0, 0)),
            pl.BlockSpec((1, npad), lambda i: (0, 0)),
            pl.BlockSpec((1, npad), lambda i: (0, 0)),
            pl.BlockSpec((1, npad), lambda i: (0, 0)),
            pl.BlockSpec((gp, npad), lambda i: (0, 0)),
            pl.BlockSpec((gp, npad), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, npad), lambda i: (i, 0)),
            pl.BlockSpec((bb, npad), lambda i: (i, 0)),
            pl.BlockSpec((bb, npad), lambda i: (i, 0)),
            pl.BlockSpec((bb, gp), lambda i: (i, 0)),
            pl.BlockSpec((bb, gp), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((x.shape[0], npad), f32),
            jax.ShapeDtypeStruct((x.shape[0], npad), f32),
            jax.ShapeDtypeStruct((x.shape[0], npad), f32),
            jax.ShapeDtypeStruct((x.shape[0], gp), jnp.int32),
            jax.ShapeDtypeStruct((x.shape[0], gp), f32),
        ],
        scratch_shapes=[pltpu.VMEM((bb, npad), f32)],
        interpret=interpret,
    )(x, cmat, qs, cls, scale, thr, grp, memberp, defaultp)
    return (raw[:b, :n], scores[:b, :n], fired[:b, :n] > 0.5,
            win[:b, :g], wscore[:b, :g])


@functools.partial(jax.jit, static_argnames=("block_b", "block_d",
                                             "interpret"))
def fused_route_dtiled(x: jnp.ndarray, centroids: jnp.ndarray,
                       classifier_mask: jnp.ndarray,
                       col_scale: jnp.ndarray, col_thr: jnp.ndarray,
                       grouped_mask: jnp.ndarray, member: jnp.ndarray,
                       default_onehot: jnp.ndarray, *,
                       qscale: jnp.ndarray | None = None,
                       block_b: int = 128, block_d: int = 256,
                       interpret: bool = False):
    """``fused_route`` for embedder dims past the VMEM budget: same
    contract, but the centroid matrix streams through VMEM in
    (N, block_d) D-chunks with a persistent f32 scratch accumulator
    instead of being fully resident.  D is zero-padded up to a
    ``block_d`` multiple (zero chunks contribute nothing, so results
    are exact); see ``_fused_route_dtiled_kernel``.
    """
    if jnp.asarray(centroids).dtype == jnp.uint8:
        raise ValueError(
            "fused_route_dtiled does not stream packed int4 stores "
            "(nibble pairs straddle D-chunk boundaries); use fused_route "
            "or the jnp lowering — kernels/ops.select_fused_variant "
            "never picks the D-tiled variant for packed stores")
    b, d = x.shape
    n = centroids.shape[0]
    g = member.shape[0]
    f32 = jnp.float32
    x, bb, nb = _pad_rows(x, block_b)
    bd = max(1, min(block_d, d))
    pad_d = (-d) % bd
    dpad = d + pad_d
    ndt = dpad // bd
    gp = max(g, 1)

    cdt = _centroid_store_dtype(centroids)
    if pad_d:
        x = jnp.pad(x, ((0, 0), (0, pad_d)))
    cmat = jnp.zeros((n, dpad), cdt).at[:, :d].set(
        jnp.asarray(centroids, cdt))
    row = lambda v: jnp.asarray(v, f32).reshape(1, n)
    qs = row(jnp.ones(n, f32) if qscale is None else qscale)
    memberf = jnp.asarray(member, f32).reshape(gp if g else 1, -1) \
        if g else jnp.zeros((1, n), f32)
    defaultf = jnp.asarray(default_onehot, f32).reshape(gp, -1) \
        if g else jnp.zeros((1, n), f32)

    raw, scores, fired, win, wscore = pl.pallas_call(
        functools.partial(_fused_route_dtiled_kernel, n_dtiles=ndt),
        grid=(nb, ndt),
        in_specs=[
            pl.BlockSpec((bb, bd), lambda i, j: (i, j)),
            pl.BlockSpec((n, bd), lambda i, j: (0, j)),  # streamed D-slab
            pl.BlockSpec((1, n), lambda i, j: (0, 0)),
            pl.BlockSpec((1, n), lambda i, j: (0, 0)),
            pl.BlockSpec((1, n), lambda i, j: (0, 0)),
            pl.BlockSpec((1, n), lambda i, j: (0, 0)),
            pl.BlockSpec((1, n), lambda i, j: (0, 0)),
            pl.BlockSpec((gp, n), lambda i, j: (0, 0)),
            pl.BlockSpec((gp, n), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, n), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, n), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, n), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, gp), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, gp), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((x.shape[0], n), f32),
            jax.ShapeDtypeStruct((x.shape[0], n), f32),
            jax.ShapeDtypeStruct((x.shape[0], n), f32),
            jax.ShapeDtypeStruct((x.shape[0], gp), jnp.int32),
            jax.ShapeDtypeStruct((x.shape[0], gp), f32),
        ],
        scratch_shapes=[pltpu.VMEM((bb, n), f32)],
        interpret=interpret,
    )(x, cmat, qs, row(classifier_mask), row(col_scale), row(col_thr),
      row(grouped_mask), memberf, defaultf)
    return (raw[:b], scores[:b], fired[:b] > 0.5,
            win[:b, :g], wscore[:b, :g])


# ---------------------------------------------------------------------------
# mesh-native shard_map body kernel: the similarity GEMM half of
# fused_route as its own launch, so the per-device work inside the
# shard_map lowering runs on the MXU while the collective softmax /
# winner reductions stay in XLA (signals/engine._sharded_route_body)
# ---------------------------------------------------------------------------


def _fused_sims_kernel(x_ref, c_ref, qs_ref, o_ref, acc_ref, *,
                       block_n: int, unpack_d: int = 0):
    """x (bb, D) · store (Npad, Ds)ᵀ -> dequantized sims (bb, Npad).
    Same N-tiled GEMM (``_tiled_sims``) as ``_fused_route_kernel``,
    without the routing tail."""
    x = x_ref[...].astype(jnp.float32)
    sims = _tiled_sims(x, c_ref, acc_ref, block_n, unpack_d)
    o_ref[...] = sims * qs_ref[...]


@functools.partial(jax.jit, static_argnames=("block_b", "block_n",
                                             "interpret"))
def fused_sims(x: jnp.ndarray, centroids: jnp.ndarray,
               qscale: jnp.ndarray | None = None, *,
               block_b: int = 128, block_n: int = 128,
               interpret: bool = False) -> jnp.ndarray:
    """Dequantized similarity GEMM as one launch: x (B, D), centroids
    (N, D) store (f32/bf16/int8, or packed-int4 uint8 with ceil(D/2)
    columns) -> (B, N) f32 ``(x @ dequant(c)ᵀ) * qscale``."""
    b, d = x.shape
    n = centroids.shape[0]
    f32 = jnp.float32
    x, bb, nb = _pad_rows(x, block_b)
    bn = max(1, min(block_n, n))
    pad_n = (-n) % bn
    npad = n + pad_n
    cdt = _centroid_store_dtype(centroids)
    packed = jnp.asarray(centroids).dtype == jnp.uint8
    dstore = centroids.shape[1]
    cmat = jnp.zeros((npad, dstore), cdt).at[:n].set(
        jnp.asarray(centroids, cdt))
    qs = jnp.ones((1, npad), f32).at[0, :n].set(
        jnp.ones(n, f32) if qscale is None
        else jnp.asarray(qscale, f32).reshape(n))
    out = pl.pallas_call(
        functools.partial(_fused_sims_kernel, block_n=bn,
                          unpack_d=d if packed else 0),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bb, d), lambda i: (i, 0)),
            pl.BlockSpec((npad, dstore), lambda i: (0, 0)),
            pl.BlockSpec((1, npad), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, npad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], npad), f32),
        scratch_shapes=[pltpu.VMEM((bb, npad), f32)],
        interpret=interpret,
    )(x, cmat, qs)
    return out[:b, :n]


# ---------------------------------------------------------------------------
# two-stage IVF kernels: coarse head scoring + top-nprobe selection, and
# the gather-then-score fine stage driven by scalar-prefetched slab ids
# ---------------------------------------------------------------------------


def _coarse_topk_kernel(x_ref, h_ref, val_ref, idx_ref, *, nprobe: int):
    """Query×heads GEMM fused with iterative top-``nprobe`` selection
    (argmax + mask-out per step; first-occurrence tie-breaking matches
    ``jax.lax.top_k``'s lower-index-first ordering).  Step ``p`` lands
    in output column ``p`` through a lane-iota select (Mosaic lowers no
    dynamic_update_slice on values)."""
    f32 = jnp.float32
    x = x_ref[...].astype(f32)                                # (bb, D)
    h = h_ref[...].astype(f32)                                # (S, D)
    sims = _dot(x, h, ((1,), (1,)))                           # (bb, S)
    cols = jax.lax.broadcasted_iota(jnp.int32, sims.shape, 1)
    bb = sims.shape[0]
    pcol = jax.lax.broadcasted_iota(jnp.int32, (bb, nprobe), 1)

    def _probe(p, carry):
        cur, vals, idxs = carry
        best = jnp.max(cur, axis=-1, keepdims=True)           # (bb, 1)
        bi = jnp.argmax(cur, axis=-1, keepdims=True).astype(jnp.int32)
        vals = jnp.where(pcol == p, best, vals)
        idxs = jnp.where(pcol == p, bi, idxs)
        cur = jnp.where(cols == bi, _NEG, cur)
        return cur, vals, idxs

    _, vals, idxs = jax.lax.fori_loop(
        0, nprobe, _probe,
        (sims, jnp.full((bb, nprobe), _NEG, f32),
         jnp.zeros((bb, nprobe), jnp.int32)))
    val_ref[...] = vals
    idx_ref[...] = idxs


@functools.partial(jax.jit, static_argnames=("nprobe", "block_b",
                                             "interpret"))
def coarse_topk(x: jnp.ndarray, heads: jnp.ndarray, nprobe: int, *,
                block_b: int = 128, interpret: bool = False):
    """Stage-1 cluster selection: x (B, D), heads (S, D) unit slab
    heads -> (values (B, nprobe) f32, indices (B, nprobe) int32), the
    top-``nprobe`` coarse Voronoi regions per query.  Oracle:
    ``jax.lax.top_k(x @ heads.T, nprobe)``."""
    b, d = x.shape
    s = heads.shape[0]
    if not 1 <= nprobe <= s:
        raise ValueError(f"nprobe must be in [1, {s}], got {nprobe}")
    x, bb, nb = _pad_rows(x, block_b)
    vals, idxs = pl.pallas_call(
        functools.partial(_coarse_topk_kernel, nprobe=nprobe),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bb, d), lambda i: (i, 0)),
            pl.BlockSpec((s, d), lambda i: (0, 0)),   # resident heads
        ],
        out_specs=[
            pl.BlockSpec((bb, nprobe), lambda i: (i, 0)),
            pl.BlockSpec((bb, nprobe), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((x.shape[0], nprobe), jnp.float32),
            jax.ShapeDtypeStruct((x.shape[0], nprobe), jnp.int32),
        ],
        interpret=interpret,
    )(x, heads)
    return vals[:b], idxs[:b]


def _ivf_route_kernel(pidx_ref, x_ref, c_ref, qs_ref, cls_ref, scale_ref,
                      thr_ref, grp_ref, member_ref, default_ref,
                      colid_ref, raw_ref, scores_ref, fired_ref, win_ref,
                      wscore_ref, acc, cls_s, scale_s, thr_s, grp_s,
                      mem_s, dflt_s, colid_s, *, nprobe: int,
                      slab_k: int, unpack_d: int = 0):
    """Gather-then-score fine stage for one (query row, probe) step.

    The grid is (B, nprobe); ``pidx_ref`` is the scalar-prefetched
    (B, nprobe) slab-id matrix, so every BlockSpec index_map below the
    store/metadata operands selects the *probed slab's* block before
    the body runs — the gather is pure DMA scheduling, no in-kernel
    indexing.  Each step dots the query row against one dequantized
    (slab_k, D) slab and stages the slab's sims + metadata into
    candidate-space VMEM scratch at probe offset ``p·slab_k`` (a lane
    multiple: signals/ivf rounds slab widths to 128); the last probe
    runs the shared ``_route_tail`` over the (1, Kc) candidate space
    and maps each group winner to the smallest *original* column id
    attaining its best score (the flat kernel's first-occurrence
    argmax, in global column order).  Only ``nprobe·slab_k`` candidate
    columns ever occupy VMEM, which is what keeps 100k+ route tables
    VMEM-resident per stage.  Query and output blocks are one
    (1, 1, ·) row of a (B, 1, ·) view, so their last two block dims
    span the array.
    """
    f32 = jnp.float32
    p = pl.program_id(1)
    x = x_ref[0].astype(f32)                                  # (1, D)
    slab = _dequant_tile(c_ref[0], unpack_d)                  # (slab_k, D)
    sims_p = _dot(x, slab, ((1,), (1,))) * qs_ref[...]        # (1, slab_k)
    span = pl.ds(pl.multiple_of(p * slab_k, slab_k), slab_k)
    acc[:, span] = sims_p
    cls_s[:, span] = cls_ref[...]
    scale_s[:, span] = scale_ref[...]
    thr_s[:, span] = thr_ref[...]
    grp_s[:, span] = grp_ref[...]
    mem_s[:, span] = member_ref[...]
    dflt_s[:, span] = default_ref[...]
    colid_s[:, span] = colid_ref[...]

    @pl.when(p == nprobe - 1)
    def _finish():
        raw, scores, fired, _, wscore = _route_tail(
            acc[...], cls_s[...], scale_s[...], thr_s[...], grp_s[...],
            mem_s[...], dflt_s[...])
        raw_ref[0] = raw
        scores_ref[0] = scores
        fired_ref[0] = fired.astype(f32)
        colid = colid_s[...]                                  # (1, Kc)

        def _wmap(row):
            sg = jnp.where(row > 0.0, scores, -1.0)
            best = jnp.max(sg, axis=-1, keepdims=True)        # (1, 1)
            cand = (row > 0.0) & (sg >= best)
            wmin = jnp.min(jnp.where(cand, colid, 3e38), axis=-1,
                           keepdims=True)
            return jnp.where(best < 0.0, 0.0, wmin)

        m = mem_s[...]
        win_ref[0] = _per_group(m, _wmap, jnp.zeros(
            (1, m.shape[0]), f32)).astype(jnp.int32)
        wscore_ref[0] = wscore


@functools.partial(jax.jit, static_argnames=("interpret",))
def ivf_route_candidates(x: jnp.ndarray, pidx: jnp.ndarray,
                         store3: jnp.ndarray, qscale_s: jnp.ndarray,
                         cls_s: jnp.ndarray, scale_s: jnp.ndarray,
                         thr_s: jnp.ndarray, grp_s: jnp.ndarray,
                         member_s: jnp.ndarray, default_s: jnp.ndarray,
                         colid_s: jnp.ndarray, *,
                         interpret: bool = False):
    """Fine-stage launch over the probed slabs (see
    ``_ivf_route_kernel``).  x: (B, D); pidx: (B, nprobe) int32 slab
    ids from the coarse stage; store3: (S, slab_k, Ds) quantized slab
    store; the ``*_s`` operands are the slab-space metadata rows from
    signals/ivf.build_ivf_tables.  -> (raw_c, scores_c, fired_c) in
    candidate space (B, nprobe·slab_k) plus (win, wscore) (B, G) with
    ``win`` already in *original* column ids."""
    b, d = x.shape
    s, slab_k, dstore = store3.shape
    nprobe = pidx.shape[1]
    kc = nprobe * slab_k
    gp = member_s.shape[0]
    f32 = jnp.float32
    packed = store3.dtype == jnp.uint8
    row = lambda w: pl.BlockSpec((1, 1, w), lambda i, p, pr: (i, 0, 0))
    slab = lambda h: pl.BlockSpec((h, slab_k),
                                  lambda i, p, pr: (0, pr[i, p]))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nprobe),
        in_specs=[
            row(d),
            pl.BlockSpec((1, slab_k, dstore),
                         lambda i, p, pr: (pr[i, p], 0, 0)),
            slab(1), slab(1), slab(1), slab(1), slab(1),
            slab(gp), slab(gp), slab(1),
        ],
        out_specs=[row(kc), row(kc), row(kc), row(gp), row(gp)],
        scratch_shapes=[
            pltpu.VMEM((1, kc), f32),                 # sims accumulator
            pltpu.VMEM((1, kc), f32),                 # cls
            pltpu.VMEM((1, kc), f32),                 # scale
            pltpu.VMEM((1, kc), f32),                 # thr
            pltpu.VMEM((1, kc), f32),                 # grp
            pltpu.VMEM((gp, kc), f32),                # member
            pltpu.VMEM((gp, kc), f32),                # default
            pltpu.VMEM((1, kc), f32),                 # colid
        ],
    )
    raw, scores, fired, win, wscore = pl.pallas_call(
        functools.partial(_ivf_route_kernel, nprobe=nprobe,
                          slab_k=slab_k, unpack_d=d if packed else 0),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, kc), f32),
            jax.ShapeDtypeStruct((b, 1, kc), f32),
            jax.ShapeDtypeStruct((b, 1, kc), f32),
            jax.ShapeDtypeStruct((b, 1, gp), jnp.int32),
            jax.ShapeDtypeStruct((b, 1, gp), f32),
        ],
        interpret=interpret,
    )(pidx.astype(jnp.int32), x.astype(f32).reshape(b, 1, d), store3,
      qscale_s, cls_s, scale_s, thr_s, grp_s, member_s, default_s,
      colid_s)
    return (raw[:, 0], scores[:, 0], fired[:, 0], win[:, 0], wscore[:, 0])


def _softmax_kernel(s_ref, inv_tau_ref, o_ref):
    z = s_ref[...].astype(jnp.float32) * inv_tau_ref[0]
    z = z - jnp.max(z, axis=-1, keepdims=True)
    e = jnp.exp(z)
    o_ref[...] = e / jnp.sum(e, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def voronoi_normalize_sims(sims: jnp.ndarray,
                           temperature: float | jnp.ndarray, *,
                           block_b: int = 128, interpret: bool = False
                           ) -> jnp.ndarray:
    """sims: (B, K) raw cosine similarities -> (B, K) Voronoi scores."""
    b, k = sims.shape
    sims, bb, nb = _pad_rows(sims, block_b)
    inv_tau = jnp.asarray([1.0 / temperature], jnp.float32)
    out = pl.pallas_call(
        _softmax_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((bb, k), lambda i: (i, 0)),
                  pl.BlockSpec((1,), lambda i: (0,))],
        out_specs=pl.BlockSpec((bb, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((sims.shape[0], k), jnp.float32),
        interpret=interpret,
    )(sims, inv_tau)
    return out[:b]
