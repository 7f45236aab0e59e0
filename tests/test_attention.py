"""Attention implementation equivalences: chunked online-softmax and
banded windowed prefill vs the full-materialized reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention as attn


def _qkv(b=2, s=64, h=4, kv=2, hd=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, kv, hd))
    v = jax.random.normal(ks[2], (b, s, kv, hd))
    pos = jnp.arange(s, dtype=jnp.int32)
    return q, k, v, pos


@pytest.mark.parametrize("window", [None, 16])
@pytest.mark.parametrize("chunk", [16, 24, 64])
def test_chunked_matches_full(window, chunk):
    q, k, v, pos = _qkv()
    scale = q.shape[-1] ** -0.5
    full = attn.attend_full(q, k, v, pos, pos, causal=True, window=window,
                            scale=scale)
    chk = attn.attend_chunked(q, k, v, pos, pos, causal=True, window=window,
                              scale=scale, chunk=chunk)
    np.testing.assert_allclose(np.asarray(full), np.asarray(chk),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("window", [8, 16, 32])
def test_banded_matches_full(window):
    q, k, v, pos = _qkv(s=64)
    scale = q.shape[-1] ** -0.5
    full = attn.attend_full(q, k, v, pos, pos, causal=True, window=window,
                            scale=scale)
    band = attn.attend_banded(q, k, v, pos, pos, window=window, scale=scale)
    np.testing.assert_allclose(np.asarray(full), np.asarray(band),
                               atol=2e-5, rtol=1e-4)


def test_gqa_group_expansion():
    """MQA (kv=1) must equal MHA where all kv heads share the same k/v."""
    q, k, v, pos = _qkv(h=4, kv=1)
    scale = q.shape[-1] ** -0.5
    out1 = attn.attend_full(q, k, v, pos, pos, causal=True, window=None,
                            scale=scale)
    k4 = jnp.repeat(k, 4, axis=2)
    v4 = jnp.repeat(v, 4, axis=2)
    out4 = attn.attend_full(q, k4, v4, pos, pos, causal=True, window=None,
                            scale=scale)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out4),
                               atol=1e-5)


def test_causality():
    """Changing future tokens must not change past outputs."""
    q, k, v, pos = _qkv(s=32)
    scale = q.shape[-1] ** -0.5
    base = attn.attend_full(q, k, v, pos, pos, causal=True, window=None,
                            scale=scale)
    k2 = k.at[:, 20:].set(jax.random.normal(jax.random.PRNGKey(9),
                                            k[:, 20:].shape))
    v2 = v.at[:, 20:].set(0.0)
    pert = attn.attend_full(q, k2, v2, pos, pos, causal=True, window=None,
                            scale=scale)
    np.testing.assert_allclose(np.asarray(base[:, :20]),
                               np.asarray(pert[:, :20]), atol=1e-6)


def test_softcap_applied():
    q, k, v, pos = _qkv(s=16)
    scale = q.shape[-1] ** -0.5
    a = attn.attend_full(q * 10, k * 10, v, pos, pos, causal=True,
                         window=None, scale=scale, softcap=5.0)
    assert not bool(jnp.isnan(a).any())


def _bidirectional_ref(q, k, v, kv_len, half):
    """Softmax over keys below each row's length (and within ``half`` of
    the query, when given), written out."""
    b, s, h, hd = q.shape
    g = h // k.shape[2]
    kk, vv = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * hd ** -0.5
    i = jnp.arange(s)
    mask = i[None, None, None, :] < kv_len[:, None, None, None]
    if half is not None:
        mask &= jnp.abs(i[:, None] - i[None, :])[None, None] <= half
    p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vv)


@pytest.mark.parametrize("s", [5, 8, 13, 64])
def test_symmetric_band_matches_masked_softmax(s):
    """Any length, not only multiples of the window; rows padded past
    their length compare on their real queries."""
    q, k, v, _ = _qkv(s=s)
    kv_len = jnp.array([s, max(1, s - 3)], jnp.int32)
    got = attn.attend_band(q, k, v, kv_len, window=8,
                           scale=q.shape[-1] ** -0.5)
    want = _bidirectional_ref(q, k, v, kv_len, 4)
    for r, n in enumerate(np.asarray(kv_len)):
        np.testing.assert_allclose(got[r, :n], want[r, :n], atol=2e-6)


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_bidirectional_chunked_matches_masked_softmax(chunk):
    q, k, v, pos = _qkv(s=40)
    kv_len = jnp.array([40, 11], jnp.int32)
    got = attn.attend_chunked(q, k, v, pos, pos, causal=False, window=None,
                              scale=q.shape[-1] ** -0.5, chunk=chunk,
                              kv_len=kv_len)
    want = _bidirectional_ref(q, k, v, kv_len, None)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_bidirectional_chunks_past_the_length_change_no_bit():
    """A row padded into further, wholly masked key chunks keeps every
    bit of its result."""
    q, k, v, pos = _qkv(s=64)
    kv_len = jnp.array([20, 20], jnp.int32)
    one = attn.attend_chunked(q[:, :32], k[:, :32], v[:, :32], pos[:32],
                              pos[:32], causal=False, window=None,
                              scale=0.25, chunk=32, kv_len=kv_len)
    two = attn.attend_chunked(q, k, v, pos, pos, causal=False, window=None,
                              scale=0.25, chunk=32, kv_len=kv_len)
    assert np.array_equal(np.asarray(one), np.asarray(two[:, :32]))
