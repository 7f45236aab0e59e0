"""Signal engine: batched evaluation of every declared signal, with
per-group aggregation semantics — lowered at bind time to one fused
tensor program.

Binding a RouterConfig to an embedder:
  * GEOMETRIC signals get centroids from their ``candidates`` strings
    (mean of candidate embeddings, normalized) — and the centroid is
    *written back* into the SignalAtom so the static taxonomy pass
    analyzes the same geometry the runtime executes.
  * CLASSIFIER signals (domain/jailbreak/pii/complexity) get prototype
    centroids from their category names / seed phrases; raw score =
    (cos+1)/2 — soft, calibration-dependent, exactly the paper's hazard.
  * CRISP signals evaluate in Python (they gate on request metadata).

Fused pipeline (the rule-table-lowering view: compile the whole policy
to dense tensors once, evaluate as a single program):

  * bind time stacks every probabilistic centroid into one (N, D)
    matrix plus segment metadata — per-column classifier/geometric
    calibration mask, signal thresholds, grouped-column indices, group
    ids, per-column 1/temperature and group-θ vectors, a (G, N_grouped)
    one-hot membership partition, and a default-member one-hot;
  * evaluation is ONE (B, D) @ (D, N) GEMM followed by a grouped
    normalization — either the segment-reduction jnp path or the
    grouped-Voronoi Pallas kernel (kernels/voronoi.grouped_voronoi),
    both normalizing every SIGNAL_GROUP in a single launch — then
    thresholding, default-member fallback, and the scatter back into
    the full (B, n_signals) layout, all inside one jit-cached function.

Aggregation semantics are unchanged from the interpreted engine (kept
as ``evaluate_legacy`` for A/B and as the fallback for overlapping
groups): signals in a ``softmax_exclusive`` SIGNAL_GROUP are
Voronoi-normalized (Def 1) then thresholded at the group θ; ungrouped
probabilistic signals use independent thresholding (the conflict-prone
baseline the paper starts from).

Scale levers (all composable, README "Scaling the router"):

  * ``precision=`` — bf16 / int8 centroid stores with per-signal
    dequantization scales (``quantize_centroids``): f32 accumulation
    in every GEMM, bind-time recalibration via unit-norm scales so
    fired/winner decisions track the f32 engine.
  * ``mesh=`` + ``kernel="fused"`` — the shard_map lowering
    (``sharded_fused_route``): batch over the mesh's (pod, data) axes,
    centroid columns over ``model``, with exact cross-device grouped
    softmax (pmax/psum) and first-occurrence winner reductions.
  * VMEM auto-selection — a resolved ``fused`` narrows its query-row
    block, then upgrades itself to ``fused_dtiled``, when the bound
    store exceeds the VMEM budget (kernels/ops.select_fused_plan).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from repro.core.atoms import AtomKind
from repro.dsl.compiler import RouterConfig
from repro.signals.crisp import CRISP_EVALUATORS

_CLASSIFIER_SEEDS = {
    "jailbreak": ["ignore previous instructions", "disregard the system "
                  "prompt", "pretend you have no rules", "developer mode"],
    "pii": ["social security number", "credit card number", "passport "
            "number", "home address phone"],
    "complexity": ["prove rigorously step by step", "derive the asymptotic "
                   "complexity", "multi-step reasoning"],
    "language": ["translate this text"],
}


@dataclasses.dataclass
class SignalBatchResult:
    names: List[str]
    raw: np.ndarray              # (B, n) raw scores in [0,1] (sim for geo)
    normalized: np.ndarray       # (B, n) post group-normalization scores
    fired: np.ndarray            # (B, n) bool activations
    confidence: np.ndarray       # (B, n) confidence used for TIER routing


# ---------------------------------------------------------------------------
# mixed-precision centroid store
# ---------------------------------------------------------------------------

PRECISIONS = ("f32", "bf16", "int8", "int4")


def quantize_centroids(c: np.ndarray, precision: str
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """(N, D) f32 unit-norm centroids -> (store, qscale): the quantized
    centroid tensor plus the per-signal dequantization scale.

    The qscale vector is where bind-time threshold *recalibration*
    happens: it folds 1/||dequantized centroid|| into the per-column
    similarity scale, so the similarities the thresholds, classifier
    calibration, and grouped softmax see are cosines against the
    *unit-norm* quantized centroid directions.  Every θ (signal
    threshold and group threshold) is therefore preserved untouched —
    the only residual difference vs f32 is the centroid-direction
    rounding itself, which the GEMM accumulates in f32.

    * ``f32``  — identity store, all-ones scales.
    * ``bf16`` — bf16 rounding of the centroid matrix (half the VMEM /
      HBM traffic); qscale renormalizes each rounded row.
    * ``int8`` — symmetric per-signal scaling to int8 (quarter the
      traffic); the per-row quantization step s = max|c| / 127 composes
      with the renormalization into one scale: qscale = s / ||q·s||.
    * ``int4`` — symmetric per-signal scaling to 4-bit (s = max|c| / 7)
      *packed*: the store is a (N, ceil(D/2)) uint8 matrix holding two
      two's-complement nibbles per byte (signals/ivf.pack_int4) — an
      eighth of the f32 traffic.  Same composed qscale recalibration,
      so thresholds are again preserved untouched.
    """
    c = np.asarray(c, np.float32)
    n = c.shape[0]
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}, "
                         f"got {precision!r}")
    if precision == "f32" or n == 0:
        return c.astype(np.float32), np.ones(n, np.float32)
    if precision == "bf16":
        store = np.asarray(jnp.asarray(c, jnp.bfloat16))
        norm = np.linalg.norm(store.astype(np.float32), axis=1)
        return store, (1.0 / np.maximum(norm, 1e-8)).astype(np.float32)
    levels = 7.0 if precision == "int4" else 127.0
    step = np.abs(c).max(axis=1) / levels                     # (N,)
    step = np.maximum(step, 1e-12)
    q = np.clip(np.rint(c / step[:, None]), -levels,
                levels).astype(np.int8)
    deq = q.astype(np.float32) * step[:, None]
    norm = np.linalg.norm(deq, axis=1)
    qscale = (step / np.maximum(norm, 1e-8)).astype(np.float32)
    if precision == "int4":
        from repro.signals.ivf import pack_int4
        return pack_int4(q), qscale
    return q, qscale


# ---------------------------------------------------------------------------
# device-table memoization: the static tensor bundle of a bound policy is
# uploaded once per (content, mesh, precision), not once per engine
# ---------------------------------------------------------------------------

_DEVICE_TABLE_CACHE: "collections.OrderedDict[tuple, Dict[str, jnp.ndarray]]" \
    = collections.OrderedDict()
_DEVICE_TABLE_CACHE_CAP = 64


def _device_tables(np_tensors: Dict[str, np.ndarray], *,
                   mesh: Optional[Mesh], precision: str
                   ) -> Dict[str, jnp.ndarray]:
    """Memoized device put: identical numpy bundles (same DSL bound to
    the same embedder) share one set of device-resident arrays instead
    of re-uploading centroid tables per SignalEngine instance."""
    h = hashlib.sha1()
    for k in sorted(np_tensors):
        v = np.ascontiguousarray(np_tensors[k])
        h.update(k.encode())
        h.update(str(v.dtype).encode())
        h.update(str(v.shape).encode())
        h.update(v.tobytes())
    key = (precision, mesh, h.hexdigest())
    hit = _DEVICE_TABLE_CACHE.get(key)
    if hit is not None:
        _DEVICE_TABLE_CACHE.move_to_end(key)
        return hit
    out = {k: jnp.asarray(v) for k, v in np_tensors.items()}
    _DEVICE_TABLE_CACHE[key] = out
    while len(_DEVICE_TABLE_CACHE) > _DEVICE_TABLE_CACHE_CAP:
        _DEVICE_TABLE_CACHE.popitem(last=False)
    return out


@jax.named_scope("signal")
def _signal_eval_core(emb: jnp.ndarray, crisp_raw: jnp.ndarray,
                      t: Dict[str, jnp.ndarray], *,
                      kernel_mode: str, interpret: bool, nprobe: int = 1,
                      block_b: int = 128
                      ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                 jnp.ndarray, jnp.ndarray]:
    """embeddings + crisp scores -> (raw, normalized, fired, confidence).

    Pure/traceable; ``t`` is the bound tensor bundle from
    ``SignalEngine._build_tensors``.  ``kernel_mode`` selects the
    probabilistic-column lowering:

    * ``"fused"``   — kernels/voronoi.fused_route: GEMM (centroids
      resident in VMEM, N-tiled), grouped softmax, thresholds and
      default fallback all in ONE Pallas launch;
    * ``"fused_dtiled"`` — kernels/voronoi.fused_route_dtiled: the same
      single launch with the centroid store streamed through VMEM in
      D-chunks (embedder dims past the VMEM budget); both tile queries
      by ``block_b`` rows (kernels/ops.select_fused_plan);
    * ``"ivf"`` / ``"ivf_fused"`` — the two-stage IVF path over the
      bind-time slab bundle (``ivf_*`` keys in ``t``): coarse
      top-``nprobe`` slab heads, then the routing tail over only the
      probed slabs' columns — jnp lowering vs the Pallas coarse+gather
      kernels (kernels/ivf.ivf_route);
    * ``"grouped"`` — XLA GEMM + the grouped-Voronoi Pallas kernel
      (PR 1's path);
    * ``"jnp"``     — XLA GEMM + segment-reduction normalization.

    All lowerings dequantize the (possibly bf16/int8/packed-int4)
    centroid store through the per-column ``qscale`` vector and scatter
    into the full (B, n_signals) layout here.
    """
    f32 = jnp.float32
    emb = emb.astype(f32)
    if kernel_mode in ("ivf", "ivf_fused"):
        from repro.kernels import ivf as _kivf
        ivf_t = {k[4:]: v for k, v in t.items() if k.startswith("ivf_")}
        raw_p, normalized_p, fired_p, _, _ = _kivf.ivf_route(
            emb, t["classifier_mask"].astype(f32), t["col_scale"],
            t["col_thr"], t["grouped_mask"], t["member_full"],
            t["default_full"], ivf_t, nprobe=nprobe,
            use_kernel=(kernel_mode == "ivf_fused"),
            interpret=interpret)
    elif kernel_mode in ("fused", "fused_dtiled"):
        from repro.kernels import voronoi as _vor
        fn = (_vor.fused_route if kernel_mode == "fused"
              else _vor.fused_route_dtiled)
        raw_p, normalized_p, fired_p, _, _ = fn(
            emb, t["centroids"], t["classifier_mask"].astype(f32),
            t["col_scale"], t["col_thr"], t["grouped_mask"],
            t["member_full"], t["default_full"], qscale=t["qscale"],
            block_b=block_b, interpret=interpret)
    else:
        raw_p, normalized_p, fired_p = _signal_eval_unfused(
            emb, t, kernel_mode=kernel_mode, interpret=interpret)
    b = emb.shape[0]
    n = raw_p.shape[1] + crisp_raw.shape[1]
    raw = jnp.zeros((b, n), f32).at[:, t["prob_cols"]].set(raw_p)
    normalized = jnp.zeros((b, n), f32).at[:, t["prob_cols"]].set(
        normalized_p)
    fired = jnp.zeros((b, n), bool).at[:, t["prob_cols"]].set(fired_p)
    if crisp_raw.shape[1]:
        crisp_raw = crisp_raw.astype(f32)
        raw = raw.at[:, t["crisp_cols"]].set(crisp_raw)
        normalized = normalized.at[:, t["crisp_cols"]].set(crisp_raw)
        fired = fired.at[:, t["crisp_cols"]].set(
            crisp_raw >= t["thr_crisp"][None, :])
    conf = jnp.where(fired, normalized, 0.0)
    return raw, normalized, fired, conf


def _signal_eval_unfused(emb: jnp.ndarray, t: Dict[str, jnp.ndarray], *,
                         kernel_mode: str, interpret: bool
                         ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """PR 1 lowering: one XLA GEMM, then grouped normalization via the
    segment-reduction jnp path or the grouped-Voronoi Pallas kernel."""
    f32 = jnp.float32
    c = t["centroids"]
    if c.dtype == jnp.uint8:                         # packed int4 store
        from repro.kernels.voronoi import unpack_int4
        c = unpack_int4(c, emb.shape[1])
    sims = jax.lax.dot_general(                      # the single GEMM (B, N)
        emb, c.astype(f32), (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=f32) * t["qscale"][None, :]
    raw_p = jnp.where(t["classifier_mask"][None, :],
                      (sims + 1.0) * 0.5, sims)
    fired_p = raw_p >= t["thr_prob"][None, :]
    normalized_p = raw_p
    n_groups = t["member"].shape[0]
    if n_groups:
        sims_g = jnp.take(sims, t["grouped_cols"], axis=1)
        if kernel_mode == "grouped":
            from repro.kernels import voronoi as _vor
            scores = _vor.grouped_voronoi(
                sims_g, t["inv_tau"], t["member"], interpret=interpret)
        else:
            z = sims_g * t["inv_tau"][None, :]
            gmax = jax.ops.segment_max(
                z.T, t["group_id"], num_segments=n_groups).T
            e = jnp.exp(z - jnp.take(gmax, t["group_id"], axis=1))
            gsum = jax.ops.segment_sum(
                e.T, t["group_id"], num_segments=n_groups).T
            scores = e / jnp.take(gsum, t["group_id"], axis=1)
        fired_g = scores > t["group_thr"][None, :]
        # default-member fallback: a group with no member above θ fires
        # its declared default — one-hot matmuls keep it batched
        group_any = jax.lax.dot_general(
            fired_g.astype(f32), t["member"],
            (((1,), (1,)), ((), ())), preferred_element_type=f32) > 0
        fallback = jax.lax.dot_general(
            (~group_any).astype(f32), t["default_onehot"],
            (((1,), (0,)), ((), ())), preferred_element_type=f32) > 0
        fired_g = fired_g | fallback
        normalized_p = normalized_p.at[:, t["grouped_cols"]].set(scores)
        fired_p = fired_p.at[:, t["grouped_cols"]].set(fired_g)
    return raw_p, normalized_p, fired_p


# jit-cached once per (shape-signature, flags) across every engine instance
_SIGNAL_EVAL = jax.jit(_signal_eval_core,
                       static_argnames=("kernel_mode", "interpret",
                                        "nprobe", "block_b"))

KERNEL_MODES = ("auto", "jnp", "grouped", "fused", "fused_dtiled",
                "ivf", "ivf_fused")


def resolve_kernel_mode(kernel: Optional[str], use_pallas: bool) -> str:
    """Map the user-facing (kernel, use_pallas) pair to a concrete
    lowering.  ``auto`` picks the fully-fused kernel on TPU (where it
    compiles) and the jnp segment path elsewhere (interpret-mode Pallas
    is emulation-slow on CPU); ``use_pallas=True`` keeps its PR 1
    meaning of the grouped-Voronoi kernel.  A resolved ``fused`` may be
    upgraded to ``fused_dtiled`` at bind time when the centroid store
    exceeds the VMEM budget (kernels/ops.select_fused_plan)."""
    if kernel is not None and kernel != "auto":
        if kernel not in KERNEL_MODES:
            raise ValueError(f"kernel must be one of {KERNEL_MODES}, "
                             f"got {kernel!r}")
        return kernel
    if use_pallas:
        return "grouped"
    return "fused" if jax.default_backend() == "tpu" else "jnp"


# ---------------------------------------------------------------------------
# shard_map lowering: batch over the mesh's data axes, routes over model.
# The grouped softmax and the per-group winner are exact across devices:
# per-group maxima ride pmax, denominators / fired-any ride psum, and the
# winner is the smallest global column index attaining the pmax'd best
# score (first-occurrence argmax semantics, matching fused_route).
# ---------------------------------------------------------------------------


BODY_KERNELS = ("auto", "jnp", "pallas")


def resolve_body_kernel(body_kernel: Optional[str] = None) -> str:
    """Per-device lowering inside the shard_map body: ``"pallas"`` runs
    the similarity GEMM as the ``fused_sims`` Pallas launch on each
    device's (Nl, D) store shard (mesh-native — the kernel itself lives
    inside the shard_map body); ``"jnp"`` is the PR 3 per-device XLA
    GEMM.  ``auto`` picks pallas on TPU, jnp elsewhere (interpret-mode
    Pallas inside shard_map is emulation-slow on CPU)."""
    if body_kernel is not None and body_kernel != "auto":
        if body_kernel not in BODY_KERNELS:
            raise ValueError(f"body_kernel must be one of {BODY_KERNELS},"
                             f" got {body_kernel!r}")
        return body_kernel
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def _sharded_route_body(model_axis: Optional[str],
                        body_kernel: str = "jnp",
                        interpret: bool = False):
    """Per-device body for the shard_map'd signal layer: the local
    similarity GEMM (f32 accumulation, qscale dequantization) plus the
    ONE shared copy of the routing semantics — kernels/voronoi.
    _route_tail with its collective hooks bound to pmax/psum/pmin over
    the model axis.  Operands are the local shards of the fused_route
    contract: x (Bl, D), c (Nl, D) store, and the (1, Nl)/(G, Nl)
    column metadata.  Returns the local (Bl, Nl) raw/scores/fired plus
    the model-replicated (Bl, G) winner index (global column space)
    and winning score.

    ``body_kernel="pallas"`` lowers the local GEMM as the
    ``fused_sims`` Pallas launch — the fused kernel running *inside*
    the shard_map body on each device's column shard, with the exact
    collective softmax unchanged on top (both lowerings feed the same
    ``_route_tail``, so they are decision-identical)."""
    from repro.kernels.voronoi import _route_tail, fused_sims

    def body(x, c, qs, cls, scale, thr, grp, mem, dflt):
        f32 = jnp.float32
        if body_kernel == "pallas":
            sims = fused_sims(x.astype(f32), c, qs,
                              interpret=interpret)             # (Bl, Nl)
        else:
            sims = jax.lax.dot_general(
                x.astype(f32), c.astype(f32), (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=f32) * qs              # (Bl, Nl)
        hooks = {}
        col_offset = 0
        if model_axis:
            hooks = dict(
                reduce_max=lambda v: jax.lax.pmax(v, model_axis),
                reduce_sum=lambda v: jax.lax.psum(v, model_axis),
                reduce_min=lambda v: jax.lax.pmin(v, model_axis))
            col_offset = jax.lax.axis_index(model_axis) * c.shape[0]
        return _route_tail(sims, cls, scale, thr, grp, mem, dflt,
                           col_offset=col_offset, **hooks)

    return body


def _mesh_batch_axes(mesh: Mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def mesh_data_size(mesh: Mesh) -> int:
    n = 1
    for a in _mesh_batch_axes(mesh):
        n *= mesh.shape[a]
    return n


def mesh_model_size(mesh: Mesh) -> int:
    return mesh.shape.get("model", 1)


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis in Auto mode.  The route programs leave
    the layout of what they return to the compiler's sharding
    propagation; ``jax.make_mesh`` builds Explicit axes, under which
    every scatter into and slice of a sharded result would have to name
    its own out_sharding."""
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


@functools.lru_cache(maxsize=32)
def _sharded_route_raw(mesh: Mesh, body_kernel: str = "jnp",
                       interpret: bool = False):
    """Jitted shard_map of the fused_route contract over ``mesh``:
    inputs must already be padded to (data-multiple B, model-multiple
    N).  Cached per (mesh, body lowering)."""
    mesh = auto_axes(mesh)
    daxes = _mesh_batch_axes(mesh)
    maxis = "model" if "model" in mesh.shape else None
    bspec = P(daxes if daxes else None, None)
    cspec = P(maxis, None)
    rspec = P(None, maxis)
    ospec = P(daxes if daxes else None, maxis)
    wspec = P(daxes if daxes else None, None)
    sh = jax.shard_map(
        _sharded_route_body(maxis, body_kernel, interpret), mesh=mesh,
        in_specs=(bspec, cspec, rspec, rspec, rspec, rspec, rspec,
                  rspec, rspec),
        out_specs=(ospec, ospec, ospec, wspec, wspec),
        check_vma=False)
    return jax.jit(sh)


def sharded_fused_route(mesh: Mesh, x, centroids, classifier_mask,
                        col_scale, col_thr, grouped_mask, member,
                        default_onehot, *, qscale=None,
                        body_kernel: Optional[str] = None,
                        interpret: bool = False):
    """Distributed twin of kernels/ops.fused_route: shards B over the
    mesh's (pod, data) axes and N over ``model``, with exact
    cross-device grouped softmax and winner reductions.  Same contract:
    -> (raw, scores, fired, win, wscore), win in global column space.

    Divisibility fallback mirrors distributed/sharding.fit_spec's
    replication semantics through dead padding: B pads up to the
    data-axes multiple (rows sliced off), N pads up to the model-axis
    multiple with columns that can never fire or win (threshold 2, no
    group membership), so uneven shapes shard instead of degrading.
    """
    f32 = jnp.float32
    x = jnp.asarray(x)
    b, _ = x.shape
    n = centroids.shape[0]
    g = member.shape[0]
    gp = max(g, 1)
    pad_b = (-b) % mesh_data_size(mesh)
    pad_n = (-n) % mesh_model_size(mesh)
    npad = n + pad_n
    if pad_b:
        x = jnp.pad(x, ((0, pad_b), (0, 0)))
    cdt = centroids.dtype if centroids.dtype in (jnp.bfloat16, jnp.int8) \
        else f32
    cmat = jnp.zeros((npad, x.shape[1]), cdt).at[:n].set(
        jnp.asarray(centroids, cdt))
    row = lambda v, fill: jnp.full((1, npad), fill, f32).at[0, :n].set(
        jnp.asarray(v, f32))
    qs = row(jnp.ones(n, f32) if qscale is None else qscale, 1.0)
    memberp = jnp.zeros((gp, npad), f32).at[:g, :n].set(
        jnp.asarray(member, f32))
    defaultp = jnp.zeros((gp, npad), f32).at[:g, :n].set(
        jnp.asarray(default_onehot, f32))
    raw, scores, fired, win, wscore = _sharded_route_raw(
        mesh, resolve_body_kernel(body_kernel), interpret)(
        x, cmat, qs, row(classifier_mask, 0.0), row(col_scale, 0.0),
        row(col_thr, 2.0), row(grouped_mask, 0.0), memberp, defaultp)
    return (raw[:b, :n], scores[:b, :n], fired[:b, :n],
            win[:b, :g], wscore[:b, :g])


@functools.lru_cache(maxsize=32)
def _sharded_signal_eval(mesh: Mesh, body_kernel: str = "jnp",
                         interpret: bool = False):
    """Jitted engine-level sharded evaluation: the shard_map'd signal
    layer plus the scatter into the full (B, n_signals) layout and the
    crisp-column merge.  Expects the bind-time padded bundle from
    ``SignalEngine._build_sharded_bundle`` and a B already padded to
    the mesh's data-axes multiple."""
    sh = _sharded_route_raw(mesh, body_kernel, interpret)

    @jax.jit
    @jax.named_scope("signal")
    def fn(emb, crisp_raw, st):
        f32 = jnp.float32
        raw_pp, norm_pp, fired_pp, _, _ = sh(
            emb.astype(f32), st["centroids"], st["qscale_row"],
            st["cls_row"], st["scale_row"], st["thr_row"],
            st["grp_row"], st["member_row"], st["default_row"])
        np_ = st["prob_cols"].shape[0]
        raw_p, norm_p = raw_pp[:, :np_], norm_pp[:, :np_]
        fired_p = fired_pp[:, :np_]
        b = emb.shape[0]
        n = np_ + st["crisp_cols"].shape[0]
        raw = jnp.zeros((b, n), f32).at[:, st["prob_cols"]].set(raw_p)
        normalized = jnp.zeros((b, n), f32).at[:, st["prob_cols"]].set(
            norm_p)
        fired = jnp.zeros((b, n), bool).at[:, st["prob_cols"]].set(
            fired_p)
        if st["crisp_cols"].shape[0]:
            cr = crisp_raw.astype(f32)
            raw = raw.at[:, st["crisp_cols"]].set(cr)
            normalized = normalized.at[:, st["crisp_cols"]].set(cr)
            fired = fired.at[:, st["crisp_cols"]].set(
                cr >= st["thr_crisp"][None, :])
        conf = jnp.where(fired, normalized, 0.0)
        return raw, normalized, fired, conf

    return fn


class SignalEngine:
    def __init__(self, config: RouterConfig, embedder, *,
                 use_pallas: bool = False,
                 kernel: Optional[str] = None,
                 precision: Optional[str] = None,
                 mesh: Optional[Mesh] = None,
                 two_stage: Optional[bool] = None,
                 nprobe: Optional[int] = None,
                 body_kernel: Optional[str] = None):
        from repro.kernels import ops
        self.cfg = config
        self.embedder = embedder
        self.use_pallas = use_pallas
        self.kernel_mode = resolve_kernel_mode(kernel, use_pallas)
        self.precision = precision or "f32"
        if self.precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}, "
                             f"got {precision!r}")
        self.mesh = mesh
        if mesh is not None and self.precision == "int4":
            # the shard_map path would have to unpack nibble pairs per
            # column shard; keep the packed store single-device
            raise ValueError("precision='int4' is not supported with a "
                             "mesh; use int8 for sharded stores")
        self.interpret = ops.default_interpret()
        # query-row block of the flat fused kernels (bind-time choice)
        self.block_b = ops.ROW_BLOCKS[0]
        self.body_kernel = resolve_body_kernel(body_kernel)
        self._two_stage_req = two_stage
        self._nprobe_req = nprobe
        self.nprobe = 1
        self.n_slabs = 0
        self.names = sorted(config.signals)
        self.index = {n: i for i, n in enumerate(self.names)}
        self.centroids: Dict[str, np.ndarray] = {}
        self._bind_centroids()
        self._build_tensors()
        if self.kernel_mode == "fused" and self._prob_names \
                and self.mesh is None:
            # VMEM-budget auto-selection: embedder dims whose centroid
            # store cannot stay resident stream through the D-tiled
            # variant; past even that, fall back to jnp.  With a mesh
            # bound the shard_map path evaluates per-device jnp (no
            # VMEM constraint), so the gate must not downgrade it away.
            # centroid_bytes is the *stored* width (0.5 for packed
            # int4), not the f32 image — the satellite fix that keeps
            # quantized stores resident up to their true footprint.
            store = self.tensors["centroids"]
            self.kernel_mode, self.block_b = ops.select_fused_plan(
                store.shape[0], self._embed_dim,
                self.tensors["member_full"].shape[0],
                centroid_bytes=ops.precision_centroid_bytes(
                    self.precision))

    # ---- binding -------------------------------------------------------------
    def _prototype_texts(self, name: str) -> List[str]:
        sig = self.cfg.signals[name]
        f = self.cfg.signal_fields.get(name, {})
        if f.get("candidates"):
            return [str(c) for c in f["candidates"]]
        if sig.categories:
            return [c.replace("_", " ") for c in sig.categories]
        if sig.signal_type in _CLASSIFIER_SEEDS:
            return _CLASSIFIER_SEEDS[sig.signal_type]
        return [name.replace("_", " ")]

    def _bind_centroids(self):
        # every prototype in one call, so an on-device embedder batches
        # them into full launches
        names = [n for n in self.names
                 if self.cfg.signals[n].kind is not AtomKind.CRISP]
        texts = [self._prototype_texts(n) for n in names]
        flat = [t for ts in texts for t in ts]
        emb = np.asarray(self.embedder.embed(flat)) if flat else None
        at = 0
        for name, ts in zip(names, texts):
            sig = self.cfg.signals[name]
            protos = emb[at: at + len(ts)]
            at += len(ts)
            c = protos.mean(axis=0)
            c = c / max(np.linalg.norm(c), 1e-8)
            self.centroids[name] = c.astype(np.float32)
            if sig.kind is AtomKind.GEOMETRIC:
                # write the live geometry back into the static atom so the
                # taxonomy pass and the runtime agree (paper fig. 3)
                self.cfg.signals[name] = dataclasses.replace(
                    sig, centroid=tuple(float(v) for v in c))

    def _build_tensors(self):
        """Lower the bound policy's signal layer to dense tensors (the
        compile-once half of the fused pipeline)."""
        self._prob_names = [n for n in self.names if n in self.centroids]
        self._crisp_names = [n for n in self.names
                             if n not in self.centroids]
        prob_index = {n: i for i, n in enumerate(self._prob_names)}
        # overlapping groups (a signal in ≥2 groups) keep sequential
        # last-wins semantics only the interpreted path reproduces
        seen: Dict[str, int] = {}
        self._fused_ok = True
        for group in self.cfg.groups.values():
            for m in group.names:
                if m in prob_index:
                    seen[m] = seen.get(m, 0) + 1
                    if seen[m] > 1:
                        self._fused_ok = False
        grouped_cols: List[int] = []
        group_id: List[int] = []
        inv_tau: List[float] = []
        group_thr: List[float] = []
        member_rows: List[Tuple[int, int]] = []       # (start, count)
        default_rows: List[Optional[int]] = []        # grouped-col index
        gi = 0
        for group in self.cfg.groups.values():
            cols = [prob_index[m] for m in group.names if m in prob_index]
            if not cols:
                continue
            start = len(grouped_cols)
            grouped_cols.extend(cols)
            group_id.extend([gi] * len(cols))
            inv_tau.extend([1.0 / group.temperature] * len(cols))
            group_thr.extend([group.threshold] * len(cols))
            gi += 1
            member_rows.append((start, len(cols)))
            drow = None
            if group.default is not None and group.default in self.index:
                pd = prob_index.get(group.default)
                if pd is not None and pd in cols:
                    drow = start + cols.index(pd)
                else:
                    # default is a declared signal outside the group's
                    # probabilistic members (crisp or non-member): only
                    # the interpreted path expresses that fallback
                    self._fused_ok = False
            default_rows.append(drow)
        ng = len(grouped_cols)
        member = np.zeros((gi, ng), np.float32)
        default_onehot = np.zeros((gi, ng), np.float32)
        for g, (start, count) in enumerate(member_rows):
            member[g, start: start + count] = 1.0
            if default_rows[g] is not None:
                default_onehot[g, default_rows[g]] = 1.0
        dim = (self.centroids[self._prob_names[0]].shape[0]
               if self._prob_names else 1)
        self._embed_dim = dim
        centroids_f32 = (
            np.stack([self.centroids[n] for n in self._prob_names])
            if self._prob_names else np.zeros((0, dim), np.float32))
        # mixed-precision centroid store + the per-signal dequantization
        # scale that carries the bind-time threshold recalibration
        centroids, qscale = quantize_centroids(centroids_f32,
                                               self.precision)
        sigs = self.cfg.signals
        # full-width per-column metadata for the fully-fused kernel
        # (kernels/voronoi.fused_route operates on the whole probabilistic
        # column space, not just the grouped subset)
        n_prob = len(self._prob_names)
        thr_prob = np.asarray([sigs[n].threshold for n in self._prob_names],
                              np.float32)
        col_scale = np.ones(n_prob, np.float32)
        col_thr = thr_prob.copy()
        grouped_mask = np.zeros(n_prob, np.float32)
        member_full = np.zeros((gi, n_prob), np.float32)
        default_full = np.zeros((gi, n_prob), np.float32)
        for j, col in enumerate(grouped_cols):
            g = group_id[j]
            col_scale[col] = inv_tau[j]
            col_thr[col] = group_thr[j]
            grouped_mask[col] = 1.0
            member_full[g, col] = 1.0
        for g, (start, count) in enumerate(member_rows):
            if default_rows[g] is not None:
                default_full[g, grouped_cols[default_rows[g]]] = 1.0
        np_tensors: Dict[str, np.ndarray] = {
            "centroids": centroids,
            "qscale": qscale,
            "classifier_mask": np.asarray(
                [sigs[n].kind is not AtomKind.GEOMETRIC
                 for n in self._prob_names], bool),
            "thr_prob": thr_prob,
            "thr_crisp": np.asarray(
                [sigs[n].threshold for n in self._crisp_names],
                np.float32),
            "prob_cols": np.asarray(
                [self.index[n] for n in self._prob_names], np.int32),
            "crisp_cols": np.asarray(
                [self.index[n] for n in self._crisp_names], np.int32),
            "grouped_cols": np.asarray(grouped_cols, np.int32),
            "group_id": np.asarray(group_id, np.int32),
            "inv_tau": np.asarray(inv_tau, np.float32),
            "group_thr": np.asarray(group_thr, np.float32),
            "member": member,
            "default_onehot": default_onehot,
            "col_scale": col_scale,
            "col_thr": col_thr,
            "grouped_mask": grouped_mask,
            "member_full": member_full,
            "default_full": default_full,
        }
        self._resolve_two_stage(np_tensors, centroids_f32)
        # effective firing threshold per signal column (self.names
        # order): group θ for grouped probabilistic signals, the atom's
        # own threshold otherwise — what `fired` actually compares
        # against, which is what the online conflict monitor must use
        eff = np.zeros(len(self.names), np.float32)
        if n_prob:
            eff[np_tensors["prob_cols"]] = col_thr
        if self._crisp_names:
            eff[np_tensors["crisp_cols"]] = np_tensors["thr_crisp"]
        self.effective_thresholds = eff
        # memoized device put: a second engine bound to the same DSL /
        # embedder / (mesh, precision) reuses the resident tables
        self.tensors: Dict[str, jnp.ndarray] = _device_tables(
            np_tensors, mesh=None, precision=self.precision)
        self.sharded_tensors: Optional[Dict[str, jnp.ndarray]] = None
        if (self.mesh is not None and self._prob_names and self._fused_ok
                and self.kernel_mode in ("fused", "fused_dtiled")):
            # only when the shard_map path can actually activate — a
            # mesh bound to a non-fused kernel must not pay a second
            # device upload of the centroid store
            self.sharded_tensors = _device_tables(
                self._build_sharded_bundle(np_tensors),
                mesh=self.mesh, precision=self.precision)

    def _resolve_two_stage(self, np_tensors: Dict[str, np.ndarray],
                           centroids_f32: np.ndarray) -> None:
        """Decide and build the two-stage IVF path at bind time.

        Activation: an explicit ``two_stage=True`` or
        ``kernel="ivf"/"ivf_fused"`` request, or — when unset — any
        fused-lowerable single-device table with
        n_prob ≥ kernels/ops.IVF_AUTO_MIN_ROUTES (the scale regime
        where the flat kernels' linear-in-N cost loses to ~sqrt(N)).
        The bundle (cluster heads, quantized slab store, slab-space
        metadata) joins ``np_tensors`` under ``ivf_*`` keys so the
        memoized device upload covers it, and ``self.nprobe`` resolves
        to the clamped user request or the recall-tuned default."""
        from repro.kernels import ops
        n_prob = len(self._prob_names)
        explicit_mode = self.kernel_mode in ("ivf", "ivf_fused")
        want = self._two_stage_req
        if want is False and explicit_mode:
            raise ValueError("two_stage=False contradicts "
                             f"kernel={self.kernel_mode!r}")
        if want is None:
            want = explicit_mode or (
                self._fused_ok and self.mesh is None
                and n_prob >= ops.IVF_AUTO_MIN_ROUTES)
        supportable = (self._fused_ok and self.mesh is None
                       and n_prob >= 8)
        if want and not supportable:
            raise ValueError(
                "two_stage routing needs a fused-lowerable config with "
                ">= 8 probabilistic signals and no mesh (the sharded "
                "path evaluates the flat table)")
        self.two_stage = bool(want)
        if not self.two_stage:
            return
        from repro.signals.ivf import build_ivf_tables, default_nprobe
        if not explicit_mode:
            self.kernel_mode = ("ivf_fused"
                                if jax.default_backend() == "tpu"
                                else "ivf")
        ivf_np = build_ivf_tables(
            centroids_f32,
            np_tensors["classifier_mask"].astype(np.float32),
            np_tensors["col_scale"], np_tensors["col_thr"],
            np_tensors["grouped_mask"], np_tensors["member_full"],
            np_tensors["default_full"], precision=self.precision)
        n_slabs = ivf_np["heads"].shape[0]
        self.n_slabs = n_slabs
        req = (default_nprobe(n_slabs) if self._nprobe_req is None
               else int(self._nprobe_req))
        self.nprobe = max(1, min(req, n_slabs))
        for k, v in ivf_np.items():
            np_tensors[f"ivf_{k}"] = v

    def set_nprobe(self, nprobe: int) -> int:
        """Runtime ``nprobe`` adjustment — the degradation-ladder
        actuator.  Clamps to ``[1, n_slabs]`` and takes effect on the
        next ``evaluate`` call (``nprobe`` is a static jit argument, so
        each distinct value selects an already- or newly-compiled
        variant; stepping between a few ladder values re-uses cached
        executables).  No-op on non-two-stage engines, where there is
        no coarse stage to narrow.  -> the nprobe actually in effect."""
        if not self.two_stage:
            return self.nprobe
        self.nprobe = max(1, min(int(nprobe), self.n_slabs))
        return self.nprobe

    def _build_sharded_bundle(self, t: Dict[str, np.ndarray]
                              ) -> Dict[str, np.ndarray]:
        """Model-axis-padded view of the probabilistic column space for
        the shard_map lowering: N pads up to the mesh's model-axis
        multiple with dead columns (threshold 2, no membership) so the
        centroid GEMM shards evenly — the divisibility fallback keeps
        results exact instead of replicating the whole table."""
        n_prob = t["centroids"].shape[0]
        dim = t["centroids"].shape[1] if t["centroids"].ndim == 2 else 1
        pad = (-n_prob) % mesh_model_size(self.mesh)
        nsh = n_prob + pad
        gi = t["member_full"].shape[0]

        def rowp(v, fill):
            out = np.full((1, nsh), fill, np.float32)
            out[0, :n_prob] = np.asarray(v, np.float32)
            return out

        store = t["centroids"]
        if pad:
            store = np.concatenate(
                [store, np.zeros((pad, dim), store.dtype)], axis=0)
        grid = np.zeros((gi, nsh), np.float32)
        grid[:, :n_prob] = t["member_full"]
        dflt = np.zeros((gi, nsh), np.float32)
        dflt[:, :n_prob] = t["default_full"]
        return {
            "centroids": store,
            "qscale_row": rowp(t["qscale"], 1.0),
            "cls_row": rowp(t["classifier_mask"].astype(np.float32), 0.0),
            "scale_row": rowp(t["col_scale"], 0.0),
            "thr_row": rowp(t["col_thr"], 2.0),
            "grp_row": rowp(t["grouped_mask"], 0.0),
            "member_row": grid,
            "default_row": dflt,
            "prob_cols": t["prob_cols"],
            "crisp_cols": t["crisp_cols"],
            "thr_crisp": t["thr_crisp"],
        }

    @property
    def fused_ok(self) -> bool:
        """True when the bound config lowers to the fused tensor path
        (always, except overlapping SIGNAL_GROUP memberships)."""
        return self._fused_ok and bool(self._prob_names)

    @property
    def sharded_active(self) -> bool:
        """True when evaluation goes through the shard_map lowering:
        a mesh was bound AND the fused kernel family was selected (the
        distributed path is gated behind ``kernel="fused"``)."""
        return (self.mesh is not None and self.fused_ok
                and self.kernel_mode in ("fused", "fused_dtiled"))

    # ---- evaluation ------------------------------------------------------------
    def embed(self, texts: Sequence[str]):
        """(B, d) unit rows: numpy from a host embedder, a device array
        from an on-device one (``EncoderEmbedder``)."""
        return self.embedder.embed(texts)

    def crisp_scores(self, texts: Sequence[str],
                     metadata: Optional[Sequence[Dict[str, Any]]] = None
                     ) -> np.ndarray:
        """(B, n_crisp) crisp scores, columns in ``_crisp_names`` order."""
        meta = metadata or [None] * len(texts)
        out = np.zeros((len(texts), len(self._crisp_names)), np.float32)
        for k, name in enumerate(self._crisp_names):
            sig = self.cfg.signals[name]
            f = self.cfg.signal_fields.get(name, {})
            fn = CRISP_EVALUATORS.get(sig.signal_type)
            if fn:
                for i, t in enumerate(texts):
                    out[i, k] = fn(t, meta[i], f)
        return out

    def evaluate(self, texts: Sequence[str],
                 metadata: Optional[Sequence[Dict[str, Any]]] = None
                 ) -> SignalBatchResult:
        if not self.fused_ok:
            return self.evaluate_legacy(texts, metadata)
        emb = self.embedder.embed(texts)
        crisp = self.crisp_scores(texts, metadata)
        if self.sharded_active:
            raw, normalized, fired, conf = self.eval_sharded(emb, crisp)
        else:
            raw, normalized, fired, conf = _SIGNAL_EVAL(
                jnp.asarray(emb), jnp.asarray(crisp), self.tensors,
                kernel_mode=self.kernel_mode, interpret=self.interpret,
                nprobe=self.nprobe, block_b=self.block_b)
        return SignalBatchResult(
            list(self.names), np.asarray(raw), np.asarray(normalized),
            np.asarray(fired), np.asarray(conf))

    def eval_sharded(self, emb: np.ndarray, crisp: np.ndarray):
        """Mesh-distributed evaluation of the bound signal layer: B
        pads up to the data-axes multiple, shards over (pod, data), and
        the probabilistic columns shard over model.  -> (raw,
        normalized, fired, conf) device arrays sliced back to B rows."""
        b = emb.shape[0]
        pad = (-b) % mesh_data_size(self.mesh)
        emb = np.asarray(emb)
        crisp = np.asarray(crisp)
        if pad:
            emb = np.pad(emb, ((0, pad), (0, 0)))
            crisp = np.pad(crisp, ((0, pad), (0, 0)))
        raw, normalized, fired, conf = _sharded_signal_eval(
            self.mesh, self.body_kernel, self.interpret)(
            jnp.asarray(emb), jnp.asarray(crisp), self.sharded_tensors)
        return raw[:b], normalized[:b], fired[:b], conf[:b]

    # ---- legacy interpreted path (A/B oracle + overlapping-group fallback) ----
    def evaluate_legacy(self, texts: Sequence[str],
                        metadata: Optional[Sequence[Dict[str, Any]]] = None
                        ) -> SignalBatchResult:
        b = len(texts)
        n = len(self.names)
        raw = np.zeros((b, n), np.float32)
        emb = np.asarray(self.embedder.embed(texts))          # (B, d)
        meta = metadata or [None] * b
        for j, name in enumerate(self.names):
            sig = self.cfg.signals[name]
            f = self.cfg.signal_fields.get(name, {})
            if sig.kind is AtomKind.CRISP:
                fn = CRISP_EVALUATORS.get(sig.signal_type)
                for i, t in enumerate(texts):
                    raw[:, j][i] = fn(t, meta[i], f) if fn else 0.0
            else:
                sims = emb @ self.centroids[name]
                if sig.kind is AtomKind.GEOMETRIC:
                    raw[:, j] = sims              # cosine, thresholded as-is
                else:                             # classifier: calibrated soft
                    raw[:, j] = (sims + 1.0) / 2.0
        normalized, fired = self._aggregate(emb, raw)
        conf = np.where(fired, normalized, 0.0)
        return SignalBatchResult(list(self.names), raw, normalized,
                                 fired, conf)

    def _aggregate(self, emb: np.ndarray, raw: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray]:
        normalized = raw.copy()
        thresholds = np.array(
            [self.cfg.signals[n].threshold for n in self.names], np.float32)
        fired = raw >= thresholds[None, :]
        for gname, group in self.cfg.groups.items():
            idx = [self.index[m] for m in group.names if m in self.index]
            if not idx:
                continue
            members = [m for m in group.names if m in self.index]
            C = np.stack([self.centroids[m] for m in members])
            sims = emb @ C.T                       # raw cosine for the group
            scores = self._voronoi(sims, group.temperature)
            for k, j in enumerate(idx):
                normalized[:, j] = scores[:, k]
                fired[:, j] = scores[:, k] > group.threshold
            if group.default is not None and group.default in self.index:
                jd = self.index[group.default]
                none_fired = ~np.any(
                    np.stack([fired[:, j] for j in idx], axis=1), axis=1)
                fired[:, jd] |= none_fired
        return normalized, fired

    def _voronoi(self, sims: np.ndarray, temperature: float) -> np.ndarray:
        if self.use_pallas:
            from repro.kernels import ops
            # platform-default interpret resolution (compiled on TPU)
            return np.asarray(ops.voronoi_normalize_sims(sims, temperature))
        z = sims / temperature
        z = z - z.max(axis=-1, keepdims=True)
        e = np.exp(z)
        return e / e.sum(axis=-1, keepdims=True)
