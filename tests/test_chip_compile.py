"""Compile the routing kernels for a described TPU v5e chip.

Interpret mode cannot see what Mosaic, the TPU kernel compiler,
refuses: blocks not aligned to the (8, 128) tile, slices of values at
dynamic offsets, kernels past the scoped-VMEM limit.  Each test lowers
one kernel at ``interpret=False`` for one chip of a described
``v5e:2x2`` topology and compiles it.  Nothing runs, so no chip is
needed.

The topology is described inside a fixture, never at import: only the
test worker that runs this file may load the TPU compiler, and every
worker must collect the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels import voronoi as vor

F32 = jnp.float32
# a 100k-route table's slab layout (signals/ivf.build_ivf_tables):
# ~sqrt(N) slabs of 640 columns, default nprobe 20, int8 store, the
# cache-miss batch of benchmarks/bench_router.py
IVF_SLABS, IVF_SLAB_K, IVF_NPROBE, IVF_B, D = 316, 640, 20, 8, 256
STORE = {"f32": (F32, 1.0, 4.0), "int8": (jnp.int8, 1.0, 1.0),
         "int4": (jnp.uint8, 0.5, 0.5)}   # dtype, columns/dim, bytes


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _route_args(sharding, b, n, d, g, precision="f32"):
    dtype, cols, _ = STORE[precision]
    s = lambda shape, dt=F32: _sds(sharding, shape, dt)
    return (s((b, d)), s((n, int(d * cols)), dtype),
            s((n,)), s((n,)), s((n,)), s((n,)), s((g, n)), s((g, n)))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _largest_n(variant, d, g, centroid_bytes, block_b=128):
    """Largest 128-multiple route count ``select_fused_variant`` still
    gives ``variant`` at a ``block_b``-row query block."""
    ns = [n for n in range(128, 16384 + 1, 128)
          if ops.select_fused_variant(n, d, g, block_b=block_b,
                                      centroid_bytes=centroid_bytes)
          == variant]
    assert ns, f"{variant} is never selected at d={d}"
    return max(ns)


def test_fused_route_at_serve_policy_shape(one_chip):
    """chip_smoke.py's serve policy: three probabilistic signals in one
    group, a 16-row routing bucket."""
    _assert_kernel(vor.fused_route.lower(
        *_route_args(one_chip, 16, 3, D, 1), interpret=False).compile())


@pytest.mark.parametrize("precision,g", [("f32", 1), ("f32", 16),
                                         ("int8", 1), ("int4", 1)])
def test_fused_route_at_vmem_budget(one_chip, precision, g):
    """The widest table the VMEM estimate keeps resident must fit the
    chip's scoped VMEM (the estimate is calibrated to Mosaic's)."""
    cb = STORE[precision][2]
    n = _largest_n("fused", D, g, cb)
    qs = _sds(one_chip, (n,))
    _assert_kernel(vor.fused_route.lower(
        *_route_args(one_chip, 128, n, D, g, precision), qscale=qs,
        interpret=False).compile())


@pytest.mark.parametrize("block_b", ops.ROW_BLOCKS[1:])
def test_fused_route_narrow_block_at_vmem_budget(one_chip, block_b):
    """Tables too wide for a 128-row block stay resident at a narrower
    one (``select_fused_plan``): the widest table the plan gives each
    block must fit when a 128-row batch is tiled by it."""
    n = _largest_n("fused", D, 1, 4.0, block_b)
    assert ops.select_fused_plan(n, D, 1) == ("fused", block_b)
    _assert_kernel(vor.fused_route.lower(
        *_route_args(one_chip, 128, n, D, 1), block_b=block_b,
        interpret=False).compile())


def test_fused_route_dtiled_at_vmem_budget(one_chip):
    d = 4096
    n = _largest_n("fused_dtiled", d, 1, 4.0)
    _assert_kernel(vor.fused_route_dtiled.lower(
        *_route_args(one_chip, 128, n, d, 1), interpret=False).compile())


@pytest.mark.parametrize("precision", ["f32", "int8"])
def test_fused_sims_shard_shape(one_chip, precision):
    """The shard_map body's GEMM: a 1024-route table over a 4-way
    model axis, 16 query rows."""
    dtype = STORE[precision][0]
    _assert_kernel(vor.fused_sims.lower(
        _sds(one_chip, (16, D)), _sds(one_chip, (256, D), dtype),
        _sds(one_chip, (256,)), interpret=False).compile())


def test_coarse_topk_100k_routes(one_chip):
    _assert_kernel(vor.coarse_topk.lower(
        _sds(one_chip, (IVF_B, D)), _sds(one_chip, (IVF_SLABS, D)),
        IVF_NPROBE, interpret=False).compile())


@pytest.mark.parametrize("precision", ["int8", "int4"])
def test_ivf_route_candidates_100k_routes(one_chip, precision):
    dtype, cols, _ = STORE[precision]
    s = lambda shape, dt=F32: _sds(one_chip, shape, dt)
    ns = IVF_SLABS * IVF_SLAB_K
    row = lambda: s((1, ns))
    _assert_kernel(vor.ivf_route_candidates.lower(
        s((IVF_B, D)), s((IVF_B, IVF_NPROBE), jnp.int32),
        s((IVF_SLABS, IVF_SLAB_K, int(D * cols)), dtype),
        row(), row(), row(), row(), row(), row(), row(), row(),
        interpret=False).compile())


@pytest.mark.parametrize("m,kp,kn", [(1, 1, 0), (3, 2, 1)])
def test_policy_argmax_gathers_rows_at_16k_routes(one_chip, m, kp, kn):
    """The policy argmax over 16,464 rules (the flood cell's policy, and
    a padded multi-term layout) compiles to one row gather per literal
    table: no serial per-index loop, no scatter, and arguments of the
    literal-index size rather than dense (term, atom) masks."""
    from repro.serving.policy import evaluate_policy
    n, b = 16464, 16
    i32 = jnp.int32
    jt = {"pos_idx": _sds(one_chip, (n, m, kp), i32),
          "neg_idx": _sds(one_chip, (n, m, kn), i32),
          "need": _sds(one_chip, (n, m), i32),
          "priority": _sds(one_chip, (n,)), "tier": _sds(one_chip, (n,))}
    compiled = jax.jit(evaluate_policy, static_argnums=(1,)).lower(
        jt, n, _sds(one_chip, (b, n), jnp.bool_),
        _sds(one_chip, (b, n))).compile()
    text = compiled.as_text()
    assert text.count(" gather(") == 1 + (kn > 0) + 1  # + the score pick
    assert " while(" not in text and " scatter(" not in text
    # fired/conf are 1.6 MB; the two dense masks were 2.17 GB
    assert compiled.memory_analysis().argument_size_in_bytes < 2 ** 23


def test_encoder_program_at_its_largest_bucket(one_chip):
    """The route path's encoder (ModernBERT-base at published widths,
    bf16) compiles for the chip as its largest launch, four rows of
    8,192 tokens.  Global layers run the online softmax over key chunks,
    so no (S, S) float32 score buffer of a row appears, and the
    program's scratch stays under 2 GiB."""
    import re

    from repro.configs.encoders import MODERNBERT_BASE as cfg
    from repro.models.encoder import init_encoder
    from repro.signals.embedder import GROUP_ROWS, _encode_core
    rows, length = 4, cfg.max_positions
    params = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: init_encoder(k, cfg),
                       jax.random.PRNGKey(0)))
    i32 = jnp.int32
    compiled = _encode_core.lower(
        params, _sds(one_chip, (rows, length), i32),
        _sds(one_chip, (rows,), i32),
        _sds(one_chip, (GROUP_ROWS, cfg.d_model)),
        _sds(one_chip, (rows,), i32), cfg=cfg).compile()
    shapes = set(re.findall(r"f32\[([0-9,]+)\]", compiled.as_text()))
    assert not [s for s in shapes
                if s.split(",").count(str(length)) >= 2], shapes
    assert any(str(length) in s.split(",") for s in shapes)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30
