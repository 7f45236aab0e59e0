"""Bidirectional global attention over right-padded rows (the encoder's
global layers): a flash-attention Pallas kernel.

Per (row, query block) the kernel runs the online softmax over key
blocks; the float32 running max, sum and accumulator live in VMEM
scratch, so no score ever leaves VMEM.  Scores are float32 from the
inputs' q·k, probabilities are cast to the inputs' dtype for P·V, and
the accumulation is float32, as in ``models/attention.attend_chunked``.

Layout: q, k and v arrive as (B, S, H, hd) and are viewed as (B, S,
H·hd), a free reshape, so a block holds every head side by side in the
lane dimension and nothing is transposed through HBM.  A lane tile of
128 holds 128 / hd heads; head j of a tile is scored with the tile's q
lanes outside head j zeroed, and its P·V is kept on head j's lanes.  A
contraction or output narrower than 128 lanes costs the matrix unit the
same passes as 128, so the masking adds no matrix work.  The heads are
unrolled, so the scheduler overlaps one head's exponentials with
another's products (a loop over the tiles ran far slower on the chip).
Every key block runs the one masked body, the mask all true before a
row's last block: each encoder program lowers the kernel, and a second,
unmasked copy of every head's body would double that host time.

Row ``b`` is real below ``kv_len[b]`` (a scalar-prefetch operand):

* key blocks past it are skipped: a ``pl.when`` skips the work, and the
  key/value ``index_map`` clamps to the row's last real block, so a
  skipped step issues no new copy; that block masks the keys at or past
  ``kv_len[b]`` in VMEM;
* query blocks wholly past it are skipped and write zeros.  Keys past a
  row's length are masked in every layer and only position 0 is pooled,
  so no real position reads them, and zeros keep them finite.

Blocks are ``BLOCK`` (the whole sequence when shorter), whatever the
batch, so a row's numbers depend on its own length alone.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38
BLOCK = 256
LANES = 128


def block(length: int) -> int:
    """Query and key block of a sequence of ``length`` positions."""
    return min(BLOCK, length)


def blocks(n: int, length: int) -> int:
    """Key blocks (as many query blocks) that hold ``n`` real positions
    of a row launched at ``length``."""
    return -(-n // block(length))


def _kernel(kv_len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, bs: int, heads: int, hd: int, tile: int, scale: float):
    b, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n = kv_len_ref[b]
    last = jnp.maximum(n - 1, 0) // bs           # the row's last key block
    live = qi * bs < n
    per_tile = tile // hd
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1) // hd

    def lanes(cols):
        """(bs, 1) columns of the tile's heads -> (bs, tile) by lane."""
        out = jnp.broadcast_to(cols[0], (bs, tile))
        for j in range(1, per_tile):
            out = jnp.where(lane == j, cols[j], out)
        return out

    @pl.when(live & (ki == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live & (ki <= last))
    def _step():
        key = jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        valid = key < n - ki * bs
        for t in range(heads // per_tile):
            cut = slice(t * tile, (t + 1) * tile)
            q, k, v = q_ref[0, :, cut], k_ref[0, :, cut], v_ref[0, :, cut]
            rs, pvs = [], []
            for j in range(per_tile):
                h = t * per_tile + j
                qh = q if per_tile == 1 else jnp.where(lane == j, q, 0)
                s = jax.lax.dot_general(
                    qh, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)      # (bs, bs)
                if scale != 1.0:
                    s = s * scale
                s = jnp.where(valid, s, NEG_INF)
                m_prev = m_ref[h]                             # (bs, 1)
                m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
                r = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                l_ref[h] = l_ref[h] * r + p.sum(axis=1, keepdims=True)
                m_ref[h] = m_new
                rs.append(r)
                pvs.append(jnp.dot(p.astype(v.dtype), v,
                                   preferred_element_type=jnp.float32))
            pv = pvs[0]
            for j in range(1, per_tile):
                pv = jnp.where(lane == j, pvs[j], pv)
            acc_ref[:, cut] = acc_ref[:, cut] * lanes(rs) + pv

    @pl.when(live & (ki == last))
    def _out():
        for t in range(heads // per_tile):
            cut = slice(t * tile, (t + 1) * tile)
            l = lanes([l_ref[t * per_tile + j] for j in range(per_tile)])
            o_ref[0, :, cut] = (acc_ref[:, cut] / jnp.maximum(l, 1e-30)
                                ).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live) & (ki == 0))
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def encoder_attention(q, k, v, kv_len, *, scale: float,
                      interpret: bool = False):
    """q, k, v (B, S, H, hd); kv_len (B,) int32 -> (B, S, H, hd): each
    position's softmax over the keys below its row's ``kv_len``, real
    positions as ``attend_chunked(..., causal=False, kv_len=kv_len)``
    gives them, positions in query blocks wholly past it zero.  Any
    length S: a launch is padded to whole blocks past every row."""
    b, s, heads, hd = q.shape
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(f"q, k, v differ: {q.shape}, {k.shape}, {v.shape}")
    tile = max(hd, min(LANES, heads * hd))
    if tile % hd or (heads * hd) % tile:
        raise ValueError(f"{heads} heads of {hd} do not tile {LANES} lanes")
    bs = block(s)
    width = heads * hd
    if math.frexp(scale)[0] == 0.5:      # a power of two scales q exactly
        q, scale = q * jnp.asarray(scale, q.dtype), 1.0
    # past every row's length, so masked: a row keeps its numbers
    pad = ((0, 0), (0, -s % bs), (0, 0))
    q, k, v = (jnp.pad(x.reshape(b, s, width), pad) for x in (q, k, v))
    sp = q.shape[1]

    def kv_map(bi, qi, ki, lens):
        last = jnp.maximum(lens[bi] - 1, 0) // bs
        live = qi * bs < lens[bi]
        return bi, jnp.where(live, jnp.minimum(ki, last), last), 0

    def q_map(bi, qi, ki, lens):
        return bi, jnp.minimum(qi, jnp.maximum(lens[bi] - 1, 0) // bs), 0

    kern = functools.partial(_kernel, bs=bs, heads=heads, hd=hd, tile=tile,
                             scale=scale)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, sp // bs, sp // bs),
            in_specs=[pl.BlockSpec((1, bs, width), q_map),
                      pl.BlockSpec((1, bs, width), kv_map),
                      pl.BlockSpec((1, bs, width), kv_map)],
            out_specs=pl.BlockSpec((1, bs, width),
                                   lambda bi, qi, ki, lens: (bi, qi, 0)),
            scratch_shapes=[pltpu.VMEM((heads, bs, 1), jnp.float32),
                            pltpu.VMEM((heads, bs, 1), jnp.float32),
                            pltpu.VMEM((bs, width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, sp, width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.clip(kv_len, 0, s).astype(jnp.int32), q, k, v)
    return out[:, :s].reshape(b, s, heads, hd)
