"""Public jit'd wrappers for the Pallas kernels.

On CPU (this container) the kernels execute with ``interpret=True``; on a
real TPU backend pass ``interpret=False`` (the default resolves by
platform).  ``use_ref=True`` routes to the pure-jnp oracles — handy for
A/B in benchmarks.
"""
from __future__ import annotations

import jax

from repro.kernels import decode_gqa as _dg
from repro.kernels import encoder_attention as _ea
from repro.kernels import ivf as _ivf
from repro.kernels import ref as _ref
from repro.kernels import voronoi as _vor
from repro.kernels import wkv6 as _wkv


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def default_interpret() -> bool:
    """Platform-default interpret flag: compiled on TPU, interpreted
    elsewhere.  Public so callers that bake the flag into a jit-static
    argument (signals/engine.py) resolve it the same way."""
    return _default_interpret()


def voronoi_scores(x, centroids, temperature, *, interpret=None,
                   use_ref=False, block_b: int = 128):
    if use_ref:
        return _ref.voronoi_scores_ref(x, centroids, temperature)
    interp = _default_interpret() if interpret is None else interpret
    return _vor.voronoi_scores(x, centroids, temperature,
                               block_b=block_b, interpret=interp)


def voronoi_normalize_sims(sims, temperature, *, interpret=None,
                           use_ref=False, block_b: int = 128):
    if use_ref:
        return _ref.voronoi_normalize_sims_ref(sims, temperature)
    interp = _default_interpret() if interpret is None else interpret
    return _vor.voronoi_normalize_sims(sims, temperature,
                                       block_b=block_b, interpret=interp)


def grouped_voronoi(sims, inv_tau, member, *, interpret=None,
                    use_ref=False, block_b: int = 128):
    """All SIGNAL_GROUPs in one launch: sims (B, N), inv_tau (N,),
    member (G, N) one-hot partition -> (B, N) grouped Voronoi scores."""
    if use_ref:
        import jax.numpy as jnp
        group_id = jnp.argmax(jnp.asarray(member), axis=0)
        return _ref.grouped_voronoi_ref(sims, inv_tau, group_id)
    interp = _default_interpret() if interpret is None else interpret
    return _vor.grouped_voronoi(sims, inv_tau, member,
                                block_b=block_b, interpret=interp)


# ---------------------------------------------------------------------------
# fused routing: resident vs D-tiled variant selection
# ---------------------------------------------------------------------------

# Mosaic's default scoped-VMEM limit on a v5e core is 16 MiB; the
# estimates below are calibrated against what it actually allocates
# (tests/test_chip_compile.py compiles the edge shapes), and the budget
# keeps a quarter of the limit spare for Mosaic's internal scratch
VMEM_BUDGET_BYTES = 12 * 2 ** 20

# route tables at or past this size auto-upgrade to the two-stage IVF
# path (coarse heads + gathered slabs): by sqrt scaling the two-stage
# working set is ~2·sqrt(N)·slab_k columns, so the crossover sits well
# below the flat kernels' VMEM ceiling
IVF_AUTO_MIN_ROUTES = 4096


def precision_centroid_bytes(precision: str) -> float:
    """Bytes per centroid *element* as stored: f32 4, bf16 2, int8 1,
    packed int4 0.5 (two columns per byte).  Float so the int4 store is
    accounted at its true footprint — feed this to the VMEM estimators
    instead of assuming an f32 store."""
    return {"f32": 4.0, "bf16": 2.0, "int8": 1.0, "int4": 0.5}[precision]


def _sublanes(rows: int) -> int:
    """Rows an f32 block occupies in VMEM: padded to the 8-row tile."""
    return -(-max(rows, 1) // 8) * 8


def _route_tail_vmem_bytes(n: int, block_b: int, g: int) -> int:
    """VMEM the routing tail holds per column, as Mosaic allocates it
    for ``kernels/voronoi._route_tail`` (measured by bisecting the
    VMEM limit of compiles for a v5e): twelve metadata rows and (1, N)
    temporaries, the (G, N) partition and default, and eight (bb, N)
    f32 buffers — the three outputs, the similarity accumulator, and
    the softmax and per-group temporaries.  The per-group loop keeps
    this flat in G."""
    return n * 4 * (8 * 12 + 2 * _sublanes(g) + 8 * _sublanes(block_b))


def fused_route_vmem_bytes(n: int, d: int, g: int = 1, *,
                           block_b: int = 128, block_n: int = 128,
                           centroid_bytes: float = 4) -> int:
    """VMEM estimate for one grid step of the fully-resident
    ``fused_route`` kernel: the whole (Npad, D) centroid store at its
    stored width (one buffer: Mosaic does not double-buffer a block
    whose index never changes), one f32
    dequantization tile for a quantized store, the double-buffered
    (bb, D) query block, and the routing tail."""
    bn = max(1, min(block_n, max(n, 1)))
    npad = n + ((-n) % bn)
    tile = bn * d * 4 if centroid_bytes < 4 else 0
    return int(npad * d * centroid_bytes
               + tile
               + 2 * block_b * d * 4
               + _route_tail_vmem_bytes(npad, block_b, g))


def fused_route_dtiled_vmem_bytes(n: int, d: int, g: int = 1, *,
                                  block_b: int = 128, block_d: int = 256,
                                  centroid_bytes: float = 4) -> int:
    """VMEM estimate for one grid step of the D-tiled variant: the
    double-buffered streamed (N, block_d) centroid slab (plus its f32
    cast when the store is quantized), the double-buffered query slab,
    and the routing tail over all N columns."""
    bd = max(1, min(block_d, max(d, 1)))
    cast = n * bd * 4 if centroid_bytes < 4 else 0
    return int(2 * n * bd * centroid_bytes
               + cast
               + 2 * block_b * bd * 4
               + _route_tail_vmem_bytes(n, block_b, g))


def select_fused_variant(n: int, d: int, g: int = 1, *,
                         block_b: int = 128, block_n: int = 128,
                         block_d: int = 256, centroid_bytes: float = 4,
                         budget_bytes: int | None = None) -> str:
    """VMEM-budget auto-selection between the fully-resident kernel,
    the D-tiled streaming variant, and the jnp fallback:
    -> ``"fused"`` | ``"fused_dtiled"`` | ``"jnp"``.

    ``centroid_bytes`` is the *stored* width (see
    ``precision_centroid_bytes``) — a 3 MB int8 store of an N×D table
    whose f32 image would be 12 MB still runs fully resident.  The
    resident kernel wins whenever the quantized store fits the budget
    (one HBM read per batch, no accumulator re-walks); past the budget
    the D-tiled variant streams D-slabs so only its (bb, N) accumulator
    and output buffers must stay resident — except for packed-int4
    stores (centroid_bytes < 1), whose nibble pairs straddle D-chunk
    boundaries and cannot be D-tiled, so those degrade straight to the
    jnp lowering.  When even the D-tiled buffers exceed the budget
    (very wide route tables), the jnp lowering is the only one that
    runs, so the selection degrades to it instead of picking a kernel
    that cannot compile."""
    budget = VMEM_BUDGET_BYTES if budget_bytes is None else budget_bytes
    resident = fused_route_vmem_bytes(
        n, d, g, block_b=block_b, block_n=block_n,
        centroid_bytes=centroid_bytes)
    if resident <= budget:
        return "fused"
    if centroid_bytes < 1:
        return "jnp"
    dtiled = fused_route_dtiled_vmem_bytes(
        n, d, g, block_b=block_b, block_d=block_d,
        centroid_bytes=centroid_bytes)
    return "fused_dtiled" if dtiled <= budget else "jnp"


# query-row blocks the flat fused kernels may tile a batch by, widest
# first.  The routing tail holds eight (bb, N) buffers, so a table too
# wide to stay resident at 128 rows can still fit at 16 — the block
# serving batches (power-of-two buckets of a few rows) run at anyway,
# since a kernel blocks a B-row batch by min(bb, B).
ROW_BLOCKS = (128, 64, 32, 16, 8)


def select_fused_plan(n: int, d: int, g: int = 1, *,
                      block_n: int = 128, block_d: int = 256,
                      centroid_bytes: float = 4,
                      budget_bytes: int | None = None) -> tuple[str, int]:
    """-> ``(variant, block_b)``: the fully-resident kernel at the
    widest ``ROW_BLOCKS`` block whose estimate fits the budget; failing
    that, the D-tiled variant at the widest block it fits; failing
    both, ``("jnp", 128)``.  A narrower block only costs batches wider
    than it more grid steps over the same resident store."""
    picks = [(select_fused_variant(n, d, g, block_b=bb, block_n=block_n,
                                   block_d=block_d,
                                   centroid_bytes=centroid_bytes,
                                   budget_bytes=budget_bytes), bb)
             for bb in ROW_BLOCKS]
    for want in ("fused", "fused_dtiled"):
        for variant, bb in picks:
            if variant == want:
                return variant, bb
    return "jnp", ROW_BLOCKS[0]


def select_route_variant(n: int, d: int, g: int = 1, *,
                         precision: str = "f32",
                         block_b: int = 128, block_n: int = 128,
                         block_d: int = 256,
                         budget_bytes: int | None = None) -> str:
    """Top-level routing-variant selection by table size + VMEM budget:
    -> ``"ivf"`` | ``"fused"`` | ``"fused_dtiled"`` | ``"jnp"``.

    Tables at or past ``IVF_AUTO_MIN_ROUTES`` go two-stage (the flat
    kernels' per-batch cost is linear in N; the IVF path's is
    ~sqrt(N)); smaller tables fall through to the flat VMEM-budget
    selection, which is cheaper than clustering for tables that fit."""
    if n >= IVF_AUTO_MIN_ROUTES:
        return "ivf"
    return select_fused_variant(
        n, d, g, block_b=block_b, block_n=block_n, block_d=block_d,
        centroid_bytes=precision_centroid_bytes(precision),
        budget_bytes=budget_bytes)


def fused_route(x, centroids, classifier_mask, col_scale, col_thr,
                grouped_mask, member, default_onehot, *, qscale=None,
                interpret=None, use_ref=False, block_b: int = 128,
                block_n: int = 128):
    """Fully-fused signal layer: GEMM (centroids resident) + grouped
    softmax + thresholds/defaults + per-group winners, one launch.
    -> (raw, scores, fired, win, wscore); see kernels/voronoi.fused_route."""
    if use_ref:
        return _ref.fused_route_ref(x, centroids, classifier_mask,
                                    col_scale, col_thr, grouped_mask,
                                    member, default_onehot, qscale=qscale)
    interp = _default_interpret() if interpret is None else interpret
    return _vor.fused_route(x, centroids, classifier_mask, col_scale,
                            col_thr, grouped_mask, member, default_onehot,
                            qscale=qscale, block_b=block_b,
                            block_n=block_n, interpret=interp)


def fused_route_dtiled(x, centroids, classifier_mask, col_scale, col_thr,
                       grouped_mask, member, default_onehot, *,
                       qscale=None, interpret=None, use_ref=False,
                       block_b: int = 128, block_d: int = 256):
    """D-tiled fused signal layer: streams (N, block_d) centroid slabs
    through a VMEM accumulator so embedder dims past the VMEM budget
    still run as one launch.  Same contract as ``fused_route``."""
    if use_ref:
        return _ref.fused_route_dtiled_ref(
            x, centroids, classifier_mask, col_scale, col_thr,
            grouped_mask, member, default_onehot, qscale=qscale,
            block_d=block_d)
    interp = _default_interpret() if interpret is None else interpret
    return _vor.fused_route_dtiled(
        x, centroids, classifier_mask, col_scale, col_thr, grouped_mask,
        member, default_onehot, qscale=qscale, block_b=block_b,
        block_d=block_d, interpret=interp)


def coarse_topk(x, heads, nprobe, *, interpret=None, use_ref=False,
                block_b: int = 128):
    """Stage-1 coarse Voronoi selection: x (B, D) × heads (S, D) ->
    (values, indices) of the top-``nprobe`` slab heads per query."""
    if use_ref:
        return _ref.coarse_topk_ref(x, heads, nprobe)
    interp = _default_interpret() if interpret is None else interpret
    return _vor.coarse_topk(x, heads, nprobe, block_b=block_b,
                            interpret=interp)


def ivf_route(x, classifier_mask, col_scale, col_thr, grouped_mask,
              member, default_onehot, ivf, *, nprobe, interpret=None,
              use_ref=False, use_kernel=False):
    """Two-stage IVF routing over a ``signals/ivf.build_ivf_tables``
    bundle: coarse top-``nprobe`` slab heads, then grouped
    softmax/thresholds/winners over only the probed slabs' columns.
    Same output contract as ``fused_route``; with ``nprobe = n_slabs``
    it is decision-identical to it.  ``use_kernel`` picks the Pallas
    coarse+gather lowering instead of the jnp one (both exist at every
    precision; the jnp path is the CPU/large-N default)."""
    if use_ref:
        return _ref.ivf_route_ref(x, classifier_mask, col_scale,
                                  col_thr, grouped_mask, member,
                                  default_onehot, ivf, nprobe=nprobe)
    interp = _default_interpret() if interpret is None else interpret
    return _ivf.ivf_route(x, classifier_mask, col_scale, col_thr,
                          grouped_mask, member, default_onehot, ivf,
                          nprobe=nprobe, use_kernel=use_kernel,
                          interpret=interp)


def decode_gqa(q, k, v, n_valid, *, interpret=None, use_ref=False,
               block_s: int = 512):
    if use_ref:
        return _ref.decode_gqa_ref(q, k, v, n_valid)
    interp = _default_interpret() if interpret is None else interpret
    return _dg.decode_gqa(q, k, v, n_valid, block_s=block_s,
                          interpret=interp)


def encoder_attention(q, k, v, kv_len, *, scale, interpret=None):
    """Bidirectional global attention over right-padded rows: q, k, v
    (B, S, H, hd), kv_len (B,) -> (B, S, H, hd); see
    kernels/encoder_attention.py."""
    interp = _default_interpret() if interpret is None else interpret
    return _ea.encoder_attention(q, k, v, kv_len, scale=scale,
                                 interpret=interp)


def wkv6(r, k, v, w, u, *, interpret=None, use_ref=False, chunk: int = 64):
    if use_ref:
        return _ref.wkv6_ref(r, k, v, w, u)
    interp = _default_interpret() if interpret is None else interpret
    return _wkv.wkv6(r, k, v, w, u, chunk=chunk, interpret=interp)
