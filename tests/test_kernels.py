"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs the pure-jnp
oracles in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _unit_rows(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    x = x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# voronoi
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b", [1, 7, 128, 200])
@pytest.mark.parametrize("k", [2, 5, 16])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_voronoi_scores_sweep(b, k, dtype):
    d = 64
    x = _unit_rows(jax.random.PRNGKey(0), (b, d), dtype)
    c = _unit_rows(jax.random.PRNGKey(1), (k, d), dtype)
    got = ops.voronoi_scores(x, c, 0.1, interpret=True)
    want = ref.voronoi_scores_ref(x, c, 0.1)
    atol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol)
    np.testing.assert_allclose(np.asarray(got).sum(-1), 1.0, atol=1e-4)


@pytest.mark.parametrize("tau", [0.05, 0.1, 1.0, 10.0])
def test_voronoi_normalize_sims_sweep(tau):
    sims = jax.random.uniform(jax.random.PRNGKey(2), (33, 6), minval=-1,
                              maxval=1)
    got = ops.voronoi_normalize_sims(sims, tau, interpret=True)
    want = ref.voronoi_normalize_sims_ref(sims, tau)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_voronoi_thm2_property_through_kernel():
    # corrected Thm 2 bound (see EXPERIMENTS.md §Thm2): θ > 1/2
    x = _unit_rows(jax.random.PRNGKey(3), (64, 32), jnp.float32)
    c = _unit_rows(jax.random.PRNGKey(4), (4, 32), jnp.float32)
    s = np.asarray(ops.voronoi_scores(x, c, 0.1, interpret=True))
    assert ((s > 0.5 + 1e-6).sum(axis=1) <= 1).all()


def _grouped_inputs(sizes, b, seed=0, taus=(0.05, 0.1, 1.0)):
    """Random sims + shuffled (non-contiguous) group layout."""
    rng = np.random.default_rng(seed)
    n = sum(sizes)
    gid = np.concatenate([[g] * s for g, s in enumerate(sizes)])
    gid = gid[rng.permutation(n)].astype(np.int32)
    member = np.zeros((len(sizes), n), np.float32)
    member[gid, np.arange(n)] = 1.0
    inv_tau = (1.0 / np.asarray(taus)[gid % len(taus)]).astype(np.float32)
    sims = rng.uniform(-1, 1, (b, n)).astype(np.float32)
    return sims, inv_tau, member, gid


@pytest.mark.parametrize("b,sizes", [
    (1, [3, 5, 8]),            # uneven multi-group
    (33, [2, 2, 2, 2]),        # many small groups, unaligned batch
    (128, [1, 4, 9, 2]),       # singleton group in the mix
    (200, [1, 1, 6]),          # mostly singletons
    (7, [16]),                 # one big group
])
def test_grouped_voronoi_parity(b, sizes):
    sims, inv_tau, member, gid = _grouped_inputs(sizes, b)
    got = ops.grouped_voronoi(jnp.asarray(sims), jnp.asarray(inv_tau),
                              jnp.asarray(member), interpret=True)
    want = ref.grouped_voronoi_ref(jnp.asarray(sims),
                                   jnp.asarray(inv_tau), gid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)
    # each group's scores sum to 1 per row
    gsum = np.asarray(got) @ member.T
    np.testing.assert_allclose(gsum, 1.0, atol=1e-4)


def test_grouped_voronoi_matches_per_group_kernel():
    """One launch over all groups == K separate single-group launches."""
    sims, inv_tau, member, gid = _grouped_inputs([3, 7, 2], 65, seed=3)
    fused = np.asarray(ops.grouped_voronoi(
        jnp.asarray(sims), jnp.asarray(inv_tau), jnp.asarray(member),
        interpret=True))
    for g in range(member.shape[0]):
        cols = np.where(gid == g)[0]
        tau = 1.0 / inv_tau[cols[0]]
        per_group = np.asarray(ops.voronoi_normalize_sims(
            jnp.asarray(sims[:, cols]), float(tau), interpret=True))
        np.testing.assert_allclose(fused[:, cols], per_group, atol=1e-5)


# ---------------------------------------------------------------------------
# fused_route (fully-fused signal layer)
# ---------------------------------------------------------------------------

def _fused_route_inputs(n, sizes, b, seed=0, d=32, n_classifier=2,
                        shuffle=True):
    """Queries + centroids + full-width metadata; ``sizes`` lays out the
    groups over the first sum(sizes) columns (post-shuffle), the rest
    stay ungrouped with independent thresholds."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(b, d)).astype(np.float32)
    x /= np.linalg.norm(x, axis=-1, keepdims=True)
    c = rng.normal(size=(n, d)).astype(np.float32)
    c /= np.linalg.norm(c, axis=-1, keepdims=True)
    cols = rng.permutation(n) if shuffle else np.arange(n)
    member = np.zeros((len(sizes), n), np.float32)
    default = np.zeros((len(sizes), n), np.float32)
    off = 0
    for g, s in enumerate(sizes):
        member[g, cols[off: off + s]] = 1.0
        default[g, cols[off]] = 1.0
        off += s
    grouped = member.sum(axis=0)
    scale = np.where(grouped > 0, 10.0, 1.0).astype(np.float32)
    thr = np.where(grouped > 0, 0.51, 0.4).astype(np.float32)
    cls = np.zeros(n, np.float32)
    if n_classifier:
        cls[cols[-n_classifier:]] = 1.0
    return x, c, cls, scale, thr, grouped.astype(np.float32), member, default


def _assert_fused_route_parity(args, *, block_n=128, block_b=128,
                               atol=1e-5):
    got = ops.fused_route(*[jnp.asarray(a) for a in args],
                          interpret=True, block_n=block_n,
                          block_b=block_b)
    want = ref.fused_route_ref(*args)
    for name, a, w in zip(("raw", "scores", "fired", "win", "wscore"),
                          got, want):
        a, w = np.asarray(a), np.asarray(w)
        if a.dtype in (np.bool_, np.int32):
            np.testing.assert_array_equal(a, w, err_msg=name)
        else:
            np.testing.assert_allclose(a, w, atol=atol, err_msg=name)


@pytest.mark.parametrize("b,n,sizes", [
    (1, 6, [3, 2]),              # tiny, one ungrouped column
    (33, 16, [4, 4, 4]),         # unaligned batch, 4 ungrouped
    (129, 24, [1, 9, 8]),        # batch one over a block, singleton group
    (7, 40, [40]),               # one big group, no ungrouped
])
def test_fused_route_parity_sweep(b, n, sizes):
    _assert_fused_route_parity(_fused_route_inputs(n, sizes, b))


@pytest.mark.parametrize("n,block_n", [
    (8, 8),         # N exactly one tile
    (9, 8),         # N one over a tile -> second (padded) tile
    (16, 8),        # N exactly two tiles
    (17, 8),        # two tiles + 1
    (128, 128),     # default tile size, exactly one
    (130, 128),     # default tile size, one over (two tiles of 128)
])
def test_fused_route_n_tiling_boundaries(n, block_n):
    """The fori_loop N-tiling must be invisible: same outputs whether N
    fits one VMEM tile or streams through several."""
    sizes = [3, n - 7, 2] if n > 9 else [3, 2]
    args = _fused_route_inputs(n, sizes, b=21, seed=n)
    _assert_fused_route_parity(args, block_n=block_n)
    # and the tiling itself must not change the result vs one big tile
    one_tile = ops.fused_route(*[jnp.asarray(a) for a in args],
                               interpret=True, block_n=max(n, 8))
    tiled = ops.fused_route(*[jnp.asarray(a) for a in args],
                            interpret=True, block_n=block_n)
    for a, w in zip(tiled, one_tile):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(w, np.float32), atol=1e-6)


def test_fused_route_singleton_group_spanning_tile_edge():
    """A singleton group whose only member sits exactly on a tile
    boundary (col == block_n) and a 2-member group straddling the edge
    (cols block_n-1 and block_n) must normalize correctly."""
    n, bn = 12, 8
    # unshuffled layout: group 0 -> cols [0..6], group 1 -> col 7 is the
    # last column of tile 0; place explicitly instead:
    args = list(_fused_route_inputs(n, [], b=9, seed=3, n_classifier=0,
                                    shuffle=False))
    member = np.zeros((2, n), np.float32)
    member[0, bn] = 1.0                       # singleton at tile edge
    member[1, bn - 1] = 1.0                   # straddles the boundary
    member[1, bn + 1] = 1.0
    default = np.zeros((2, n), np.float32)
    default[0, bn] = 1.0
    grouped = member.sum(axis=0)
    args[5] = grouped.astype(np.float32)
    args[3] = np.where(grouped > 0, 10.0, 1.0).astype(np.float32)
    args[4] = np.where(grouped > 0, 0.51, 0.4).astype(np.float32)
    args[6], args[7] = member, default
    _assert_fused_route_parity(tuple(args), block_n=bn)
    raw, scores, fired, win, wscore = ops.fused_route(
        *[jnp.asarray(a) for a in args], interpret=True, block_n=bn)
    # softmax over the singleton is exactly 1 and it always fires
    np.testing.assert_allclose(np.asarray(scores)[:, bn], 1.0, atol=1e-6)
    assert np.asarray(fired)[:, bn].all()
    # the straddling pair sums to 1 per row
    pair = np.asarray(scores)[:, [bn - 1, bn + 1]].sum(axis=1)
    np.testing.assert_allclose(pair, 1.0, atol=1e-5)
    assert (np.asarray(win)[:, 0] == bn).all()


def test_fused_route_no_groups():
    """G == 0: pure independent thresholding, winner outputs empty."""
    args = _fused_route_inputs(10, [], b=5, seed=7)
    _assert_fused_route_parity(args)
    raw, scores, fired, win, wscore = ops.fused_route(
        *[jnp.asarray(a) for a in args], interpret=True)
    assert win.shape == (5, 0) and wscore.shape == (5, 0)
    np.testing.assert_allclose(np.asarray(scores), np.asarray(raw))


def test_fused_route_matches_composed_kernels():
    """fused_route's grouped scores == GEMM + grouped_voronoi (the PR 1
    two-launch lowering) on the grouped columns."""
    args = _fused_route_inputs(20, [5, 1, 8], b=65, seed=11,
                               n_classifier=0)
    x, c = args[0], args[1]
    member = args[6]
    gid = member.argmax(axis=0)
    scores = np.asarray(ops.fused_route(
        *[jnp.asarray(a) for a in args], interpret=True)[1])
    sims = jnp.asarray((x @ c.T).astype(np.float32))
    two_launch = np.asarray(ops.grouped_voronoi(
        sims, jnp.asarray(args[3]), jnp.asarray(member), interpret=True))
    grouped_cols = member.sum(axis=0) > 0
    np.testing.assert_allclose(scores[:, grouped_cols],
                               two_launch[:, grouped_cols], atol=1e-5)
    del gid


# ---------------------------------------------------------------------------
# fused_route_dtiled (D-chunk streaming variant)
# ---------------------------------------------------------------------------

def _assert_dtiled_parity(args, *, block_d, block_b=128, atol=1e-5):
    got = ops.fused_route_dtiled(*[jnp.asarray(a) for a in args],
                                 interpret=True, block_d=block_d,
                                 block_b=block_b)
    want = ref.fused_route_dtiled_ref(*args, block_d=block_d)
    for name, a, w in zip(("raw", "scores", "fired", "win", "wscore"),
                          got, want):
        a, w = np.asarray(a), np.asarray(w)
        if a.dtype in (np.bool_, np.int32):
            np.testing.assert_array_equal(a, w, err_msg=name)
        else:
            np.testing.assert_allclose(a, w, atol=atol, err_msg=name)


@pytest.mark.parametrize("d,block_d", [
    (32, 32),        # D exactly one tile -> single chunk
    (33, 32),        # D one over a tile -> padded second chunk
    (64, 32),        # two exact chunks
    (65, 32),        # two chunks + 1
    (256, 32),       # D >> tile: 8 streamed chunks
    (300, 64),       # uneven D >> tile
])
def test_fused_route_dtiled_tile_boundaries(d, block_d):
    """The D-chunk accumulator must be invisible: bitwise-equal fired
    masks and winners vs the chunk-accumulated oracle at every tile
    edge (D == tile, tile + 1, D >> tile)."""
    args = _fused_route_inputs(16, [4, 4, 4], b=33, seed=d, d=d)
    _assert_dtiled_parity(args, block_d=block_d)


@pytest.mark.parametrize("b,n,sizes", [
    (1, 6, [3, 2]),
    (129, 24, [1, 9, 8]),        # batch one over a block, singleton group
    (7, 40, [40]),               # one big group, no ungrouped
])
def test_fused_route_dtiled_matches_resident(b, n, sizes):
    """Streaming the centroids through D-chunks must agree with the
    fully-resident kernel on decisions (bitwise) and scores (ulp)."""
    args = _fused_route_inputs(n, sizes, b, seed=b + n, d=96)
    tiled = ops.fused_route_dtiled(*[jnp.asarray(a) for a in args],
                                   interpret=True, block_d=32)
    resident = ops.fused_route(*[jnp.asarray(a) for a in args],
                               interpret=True)
    for name, a, w in zip(("raw", "scores", "fired", "win", "wscore"),
                          tiled, resident):
        a = np.asarray(a, np.float32)
        w = np.asarray(w, np.float32)
        np.testing.assert_allclose(a, w, atol=1e-5, err_msg=name)
    np.testing.assert_array_equal(np.asarray(tiled[2]),
                                  np.asarray(resident[2]), err_msg="fired")
    np.testing.assert_array_equal(np.asarray(tiled[3]),
                                  np.asarray(resident[3]), err_msg="win")


def test_fused_route_dtiled_no_groups():
    args = _fused_route_inputs(10, [], b=5, seed=7, d=80)
    _assert_dtiled_parity(args, block_d=32)
    out = ops.fused_route_dtiled(*[jnp.asarray(a) for a in args],
                                 interpret=True, block_d=32)
    assert out[3].shape == (5, 0) and out[4].shape == (5, 0)


def test_select_fused_variant_budget():
    """Auto-selection: small stores stay resident, stores past the VMEM
    budget stream through the D-tiled variant, and route tables so wide
    that even the D-tiled accumulator spills degrade to jnp; quantized
    stores fit a proportionally larger N×D."""
    assert ops.select_fused_variant(64, 256) == "fused"
    assert ops.select_fused_variant(512, 16384) == "fused_dtiled"
    # N so large the (bb, N) accumulator itself exceeds VMEM: only the
    # jnp lowering still runs
    assert ops.select_fused_variant(32768, 64) == "jnp"
    # explicit tiny budget: nothing fits -> jnp fallback
    assert ops.select_fused_variant(64, 256,
                                    budget_bytes=1 << 10) == "jnp"
    # int8 store is 4x smaller: a shape that spills in f32 can stay
    # resident at centroid_bytes=1
    n, d = 512, 4096
    assert ops.select_fused_variant(n, d, centroid_bytes=4) \
        == "fused_dtiled"
    assert ops.select_fused_variant(n, d, centroid_bytes=1) == "fused"


def test_select_fused_plan_narrows_the_row_block():
    """A table too wide for a resident 128-row block keeps the resident
    kernel at the widest narrower block that fits, before the D-tiled
    variant or jnp are considered."""
    assert ops.select_fused_plan(64, 256) == ("fused", 128)
    n = 3000
    assert ops.select_fused_variant(n, 256) == "jnp"
    variant, bb = ops.select_fused_plan(n, 256)
    assert variant == "fused" and bb < 128
    assert ops.select_fused_variant(n, 256, block_b=bb) == "fused"
    assert ops.select_fused_variant(n, 256, block_b=2 * bb) != "fused"
    assert ops.select_fused_plan(512, 16384) == ("fused_dtiled", 128)
    assert ops.select_fused_plan(64, 256, budget_bytes=1 << 10) \
        == ("jnp", 128)


def test_engine_routes_a_narrow_block_table_like_jnp():
    """A table past the 128-row resident budget binds the fused kernel
    at a narrower block; a batch wider than that block tiles by it and
    routes exactly as the jnp lowering does."""
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    from benchmarks.bench_router import make_dsl
    from repro.serving.router import RouterService
    dsl = make_dsl(3000)
    svc = {k: RouterService(dsl, load_backends=False, validate=False,
                            kernel=k) for k in ("fused", "jnp")}
    eng = svc["fused"].engine
    assert eng.kernel_mode == "fused" and eng.block_b < 128
    queries = [f"query about topic {i} alpha" for i in range(eng.block_b + 8)]
    np.testing.assert_array_equal(svc["fused"].route_indices(queries),
                                  svc["jnp"].route_indices(queries))


# ---------------------------------------------------------------------------
# quantized centroid stores through the fused kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precision", ["bf16", "int8"])
@pytest.mark.parametrize("variant", ["fused", "fused_dtiled"])
def test_fused_route_quantized_store_matches_oracle(precision, variant):
    """bf16/int8 centroid stores + per-signal dequant scales must match
    the oracle fed the same quantized inputs bitwise on fired/win."""
    from repro.signals.engine import quantize_centroids
    args = list(_fused_route_inputs(14, [5, 4], b=21, seed=3, d=64))
    store, qscale = quantize_centroids(args[1], precision)
    args[1] = store
    jargs = [jnp.asarray(a) for a in args]
    qs = jnp.asarray(qscale)
    if variant == "fused":
        got = ops.fused_route(*jargs, qscale=qs, interpret=True)
        want = ref.fused_route_ref(*args, qscale=qscale)
    else:
        got = ops.fused_route_dtiled(*jargs, qscale=qs, interpret=True,
                                     block_d=16)
        want = ref.fused_route_dtiled_ref(*args, qscale=qscale,
                                          block_d=16)
    for name, a, w in zip(("raw", "scores", "fired", "win", "wscore"),
                          got, want):
        a, w = np.asarray(a), np.asarray(w)
        if a.dtype in (np.bool_, np.int32):
            np.testing.assert_array_equal(a, w, err_msg=name)
        else:
            np.testing.assert_allclose(a, w, atol=1e-5, err_msg=name)


def test_quantize_centroids_unit_norm_recalibration():
    """The dequantization scale folds in 1/||deq|| — the bind-time
    threshold recalibration: effective centroids present unit norm, so
    every θ carries over from f32 untouched."""
    from repro.signals.engine import quantize_centroids
    rng = np.random.default_rng(0)
    c = rng.normal(size=(9, 48)).astype(np.float32)
    c /= np.linalg.norm(c, axis=1, keepdims=True)
    for prec in ("bf16", "int8"):
        store, qscale = quantize_centroids(c, prec)
        eff = store.astype(np.float32) * qscale[:, None]
        np.testing.assert_allclose(np.linalg.norm(eff, axis=1), 1.0,
                                   atol=1e-5)
        # direction error stays small (the only residual vs f32)
        cos = (eff * c).sum(axis=1)
        assert (cos > 0.995).all(), prec
    store, qscale = quantize_centroids(c, "f32")
    np.testing.assert_array_equal(store, c)
    np.testing.assert_array_equal(qscale, np.ones(9, np.float32))


# ---------------------------------------------------------------------------
# decode GQA
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,kv,hd,s", [
    (1, 4, 4, 32, 64),        # MHA
    (2, 8, 2, 64, 128),       # GQA
    (3, 8, 1, 32, 300),       # MQA, ragged S
    (2, 16, 4, 128, 1024),    # bigger, aligned
])
@pytest.mark.parametrize("block_s", [64, 128])
def test_decode_gqa_sweep(b, h, kv, hd, s, block_s):
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, h, hd))
    k = jax.random.normal(ks[1], (b, s, kv, hd))
    v = jax.random.normal(ks[2], (b, s, kv, hd))
    n_valid = s - 7 if s > 8 else s
    got = ops.decode_gqa(q, k, v, n_valid, interpret=True, block_s=block_s)
    want = ref.decode_gqa_ref(q, k, v, n_valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-5, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_gqa_dtypes(dtype):
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (2, 4, 32)).astype(dtype)
    k = jax.random.normal(ks[1], (2, 96, 2, 32)).astype(dtype)
    v = jax.random.normal(ks[2], (2, 96, 2, 32)).astype(dtype)
    got = ops.decode_gqa(q, k, v, 96, interpret=True, block_s=32)
    want = ref.decode_gqa_ref(q, k, v, 96)
    atol = 5e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=atol, rtol=1e-2)


def test_decode_gqa_masks_invalid_slots():
    """Garbage beyond n_valid must not leak into the output."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 2, 16))
    k = jax.random.normal(ks[1], (1, 64, 1, 16))
    v = jax.random.normal(ks[2], (1, 64, 1, 16))
    k2 = k.at[:, 40:].set(1e3)
    v2 = v.at[:, 40:].set(-1e3)
    a = ops.decode_gqa(q, k, v, 40, interpret=True, block_s=32)
    b_ = ops.decode_gqa(q, k2, v2, 40, interpret=True, block_s=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-6)


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,n,chunk", [
    (1, 64, 2, 32, 32),
    (2, 128, 4, 64, 64),
    (2, 96, 1, 16, 32),
    (1, 256, 2, 64, 128),
])
def test_wkv6_sweep(b, s, h, n, chunk):
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    r = jax.random.normal(ks[0], (b, s, h, n))
    k = jax.random.normal(ks[1], (b, s, h, n))
    v = jax.random.normal(ks[2], (b, s, h, n))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, h, n))) * 0.55 + 0.4
    u = jax.random.normal(ks[4], (h, n)) * 0.1
    got = ops.wkv6(r, k, v, w, u, interpret=True, chunk=chunk)
    want = ref.wkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-3, rtol=1e-3)


def test_wkv6_decay_extremes():
    """w→1 (no decay) and w→small must both stay finite and correct."""
    b, s, h, n = 1, 64, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    r = jax.random.normal(ks[0], (b, s, h, n))
    k = jax.random.normal(ks[1], (b, s, h, n))
    v = jax.random.normal(ks[2], (b, s, h, n))
    u = jnp.zeros((h, n))
    for wval in (0.999, 0.05):
        w = jnp.full((b, s, h, n), wval)
        got = ops.wkv6(r, k, v, w, u, interpret=True, chunk=32)
        want = ref.wkv6_ref(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-3, rtol=5e-3)


def test_wkv6_matches_model_chunked_path():
    """models/rwkv6.wkv_chunked (the jnp chunked form) and the Pallas
    kernel implement the same closed form."""
    from repro.models.rwkv6 import wkv_chunked
    b, s, h, n = 2, 128, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(10), 5)
    r = jax.random.normal(ks[0], (b, s, h, n))
    k = jax.random.normal(ks[1], (b, s, h, n))
    v = jax.random.normal(ks[2], (b, s, h, n))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, h, n))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (h, n)) * 0.1
    state = jnp.zeros((b, h, n, n))
    y_jnp, _ = wkv_chunked(r, k, v, w, u, state, 32)
    y_pl = ops.wkv6(r, k, v, w, u, interpret=True, chunk=32)
    np.testing.assert_allclose(np.asarray(y_jnp), np.asarray(y_pl),
                               atol=2e-3, rtol=1e-3)
