"""ModernBERT on the route path at its pattern in small (d 64, 4 heads
of 16, 6 layers: two periods of global, local, local; window 8;
vocabulary 512): the encoder against the plain float32 reference
(``tests/encoder_ref.py``), a text's embedding against its batch-mates,
the router deciding with the encoder, and the encoder's counters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import encoder_ref as ref
from repro.configs.encoders import MODERNBERT_BASE, modernbert
from repro.kernels import ops as kops
from repro.models.encoder import encode, init_encoder
from repro.serving import router as router_mod
from repro.serving.router import RouterService
from repro.signals.embedder import GROUP_ROWS, EncoderEmbedder

SEED = 7


def small_modernbert(**overrides):
    return modernbert(**dict(
        dict(name="modernbert-small", hidden_size=64, num_hidden_layers=6,
             num_attention_heads=4, intermediate_size=96, vocab_size=512,
             max_position_embeddings=512, local_attention=8,
             dtype="float32", attn_chunk=16), **overrides))


CFG = small_modernbert()
SHAPES = dict(hidden_size=64, num_hidden_layers=6, num_attention_heads=4,
              intermediate_size=96, vocab_size=512,
              global_attn_every_n_layers=3, local_attention=8,
              global_rope_theta=160_000.0, local_rope_theta=10_000.0,
              norm_eps=1e-5, dtype="float32")
# Both sides are float32 and differ only in the order of summation (the
# online softmax over key chunks, the band's blocks, how products fuse):
# about 2e-7 a component over six layers.  1e-5 leaves fifty times that
# and sits far under what bf16 activations move (test below).
TOL = 1e-5


@pytest.fixture(scope="module")
def ref_weights():
    return ref.weights(SHAPES, SEED)


def _program(cfg, tokens):
    n = len(tokens)
    width = max(64, n)
    row = np.zeros((1, width), np.int32)
    row[0, :n] = tokens
    params = init_encoder(jax.random.PRNGKey(SEED), cfg)
    return np.asarray(encode(params, cfg, jnp.asarray(row),
                             jnp.asarray([n], jnp.int32)))[0]


def _reference(w, tokens):
    with jax.default_matmul_precision("highest"):
        return ref.embed_one(SHAPES, w, tokens)


@pytest.mark.parametrize("n", [1, 7, 8, 9, 37, 64])
def test_embedding_matches_reference(ref_weights, n):
    """Lengths below, at and past the window (8) and its half-band."""
    tokens = np.random.default_rng(n).integers(0, 509, size=n)
    got = _program(CFG, tokens)
    want = _reference(ref_weights, tokens)
    assert np.abs(got - want).max() < TOL
    assert abs(np.linalg.norm(got) - 1.0) < 1e-6


@pytest.mark.parametrize("chunk,kernel", [(16, True), (64, False)])
def test_global_layers_take_the_kernel_past_one_key_chunk(
        ref_weights, monkeypatch, chunk, kernel):
    """A 64-position launch runs the global layers' attention kernel when
    it is longer than a key chunk and the chunk scan otherwise; both
    match the reference."""
    calls, real = [], kops.encoder_attention

    def counted(*a, **k):
        calls.append(a[0].shape)
        return real(*a, **k)
    monkeypatch.setattr(kops, "encoder_attention", counted)
    tokens = np.random.default_rng(37).integers(0, 509, size=37)
    got = _program(small_modernbert(attn_chunk=chunk), tokens)
    assert bool(calls) == kernel
    assert np.abs(got - _reference(ref_weights, tokens)).max() < TOL


def test_bf16_activations_fall_outside_the_tolerance():
    tokens = np.random.default_rng(3).integers(0, 509, size=37)
    shapes = dict(SHAPES, dtype="bfloat16")
    w = ref.weights(shapes, SEED)
    with jax.default_matmul_precision("highest"):
        want = ref.embed_one(shapes, w, tokens)
    got = _program(small_modernbert(dtype="bfloat16"), tokens)
    assert np.abs(got - want).max() > 10 * TOL


@pytest.fixture(scope="module")
def embedder():
    return EncoderEmbedder(CFG, seed=SEED)


def _text(rng, n_words):
    return " ".join(f"w{i}" for i in rng.integers(0, 5000, size=n_words))


def test_embedding_is_bitwise_the_same_whatever_its_batch_mates(embedder):
    rng = np.random.default_rng(0)
    text = _text(rng, 20)
    alone = np.asarray(embedder.embed([text]))[0]
    short = [_text(rng, int(k)) for k in rng.integers(1, 60, size=15)]
    long_ = [_text(rng, int(k)) for k in rng.integers(70, 300, size=5)]
    for mates, at in ((short, 0), (short, 7), (short, 12), (long_, 5),
                      (short[:3] + long_, 2)):
        batch = mates[:at] + [text] + mates[at:]
        got = np.asarray(embedder.embed(batch))[at]
        assert np.array_equal(got, alone)
    # the text's bucket took one full launch of GROUP_ROWS rows there,
    # and one row alone here
    assert (GROUP_ROWS, 64, list(range(16))) in embedder.plan(
        [len(embedder.tokenize(t)) for t in short + [text]])


def test_launch_plan_and_programs():
    emb = EncoderEmbedder.__new__(EncoderEmbedder)
    emb.max_len = 8192
    assert emb.bucket(2) == 64 and emb.bucket(65) == 128
    assert emb.bucket(8192) == 8192
    # a bucket fills full launches and launches what is left one by one:
    # no row is padding
    plan = emb.plan([10] * 17 + [100] * 3 + [5000] * 5)
    assert plan == [(16, 64, list(range(16))), (1, 64, [16]),
                    (1, 128, [17]), (1, 128, [18]), (1, 128, [19]),
                    (4, 8192, [20, 21, 22, 23]), (1, 8192, [24])]
    progs = emb.programs(64, 8192)
    assert len(progs) == 16
    assert (16, 2048) in progs and (8, 4096) in progs \
        and (4, 8192) in progs and max(r * n for r, n in progs) == 32768


DSL_NAMES = [f"s{i}" for i in range(8)]


def _table(rng):
    desc = {n: _text(rng, 12) for n in DSL_NAMES}
    dsl = "\n".join(
        [f'SIGNAL embedding {n} {{ candidates: ["{desc[n]}"] '
         'threshold: 0.999 }' for n in DSL_NAMES]
        + ["SIGNAL_GROUP g { semantics: softmax_exclusive temperature: "
           f"0.02 threshold: 0.51 members: [{', '.join(DSL_NAMES)}] "
           "default: s0 }"]
        + [f'ROUTE r{i} {{ PRIORITY {100 + i} WHEN embedding("{n}") '
           'MODEL "m" }' for i, n in enumerate(DSL_NAMES)]
        + ['GLOBAL { default_model: "m" }'])
    return desc, dsl


def test_router_with_the_encoder_decides_as_the_reference(ref_weights,
                                                         embedder):
    rng = np.random.default_rng(1)
    desc, dsl = _table(rng)
    svc = RouterService(dsl, embedder=embedder, load_backends=False,
                        validate=False)
    picks = rng.integers(0, len(DSL_NAMES), size=12)
    queries = [desc[DSL_NAMES[p]] + " " + _text(rng, int(k))
               for p, k in zip(picks, rng.integers(1, 100, size=12))]
    got = svc.route(queries)

    def emb(texts):
        return np.stack([_reference(ref_weights, embedder.tokenize(t))
                         for t in texts])
    cent = emb([desc[n] for n in DSL_NAMES])
    p = np.exp((emb(queries) @ cent.T) / 0.02)
    p /= p.sum(1, keepdims=True)
    want = np.where(p.max(1) > 0.51, p.argmax(1), 0)
    margin = np.abs(p - 0.51).min(1)
    clear = margin > 1e-3
    assert clear.sum() >= 10
    assert [g for g, c in zip(got, clear) if c] == \
        [f"r{w}" for w, c in zip(want, clear) if c]


def test_encoder_output_reaches_the_route_program_on_the_device(embedder,
                                                                monkeypatch):
    rng = np.random.default_rng(2)
    _, dsl = _table(rng)
    svc = RouterService(dsl, embedder=embedder, load_backends=False,
                        validate=False)
    seen = {}
    inner_embed, inner_core = embedder.embed, router_mod._route_core

    def embed(texts):
        seen["emb"] = inner_embed(texts)
        return seen["emb"]

    def core(emb, *args, **kwargs):
        seen["core"] = emb
        return inner_core(emb, *args, **kwargs)

    monkeypatch.setattr(embedder, "embed", embed)
    monkeypatch.setattr(router_mod, "_route_core", core)
    svc.route([_text(rng, 9) for _ in range(3)])
    assert isinstance(seen["emb"], jax.Array)
    assert isinstance(seen["core"], jax.Array)
    assert seen["core"].shape[0] == 4        # padded to the row bucket
    assert np.array_equal(np.asarray(seen["core"])[:3],
                          np.asarray(seen["emb"]))


def test_encoder_telemetry_counts_real_and_padded_tokens(embedder):
    rng = np.random.default_rng(4)
    _, dsl = _table(rng)
    svc = RouterService(dsl, embedder=embedder, load_backends=False,
                        validate=False)
    before = svc.telemetry()["encoder"]
    words = [10, 70, 70, 200, 3] + [10] * 16
    svc.route([_text(rng, w) for w in words[:5]])
    svc.route([_text(rng, w) for w in words[5:]])
    after = svc.telemetry()["encoder"]
    delta = {k: after[k] - before[k] for k in
             ("calls", "launches", "sequences", "tokens", "launched_tokens")}
    # tokens are the words and [CLS], [SEP]; the first call launches each
    # text alone (64, 64, 128, 128, 256), the second one full launch of
    # 16 rows of 64
    assert delta == {"calls": 2, "launches": 6, "sequences": 21,
                     "tokens": sum(words) + 2 * 21,
                     "launched_tokens": 640 + 16 * 64}
    assert after["max_len"] >= 202
    assert after["pad_share"] == pytest.approx(
        1 - after["tokens"] / after["launched_tokens"])
    got = {k: after["bucket_launches"].get(k, 0)
           - before["bucket_launches"].get(k, 0) for k in (64, 128, 256)}
    assert got == {64: 3, 128: 2, 256: 1}
    enc = after["phases"]["router.encode"]
    assert enc["n"] == before["phases"]["router.encode"]["n"] + 2
    assert "router.encode" not in svc.telemetry()["route"]["phases"]


@pytest.mark.parametrize("chunk,run", [(16, 1 + 4 + 9), (1024, 1 + 4 + 16)])
def test_global_block_counters_match_a_hand_count(chunk, run):
    """Bucket 1,024 holds 4 x 4 blocks of 256 and a 602-token row's 3 x 3
    real ones; bucket 512, 2 x 2 of 2 x 2; bucket 64 is one block.  The
    kernel computes the real ones; at a key chunk of 1,024 no launch runs
    it and the chunk scan computes every block.  Two global layers count
    each."""
    emb = EncoderEmbedder(small_modernbert(max_position_embeddings=1024,
                                           attn_chunk=chunk), seed=SEED)
    rng = np.random.default_rng(5)
    emb.embed([_text(rng, w) for w in (600, 10, 300)])
    st = emb.telemetry()
    assert [(r, n) for r, n, _ in emb.plan([602, 12, 302])] == \
        [(1, 64), (1, 512), (1, 1024)]
    assert st["global_blocks"] == 2 * (1 + 4 + 16)
    assert st["global_blocks_run"] == 2 * run


def test_published_config():
    c = MODERNBERT_BASE
    specs = c.layer_specs()
    assert len(specs) == 22
    glob = [i for i, s in enumerate(specs) if s.window is None]
    assert glob == list(range(0, 22, 3))
    assert all(s.rope_theta == (160_000.0 if s.window is None else 10_000.0)
               for s in specs)
    assert not specs[0].attn_norm and all(s.attn_norm for s in specs[1:])
    assert all(s.window in (None, 128) and not s.causal for s in specs)
    per_layer = 4 * 768 * 768 + 3 * 768 * 1152
    assert 22 * per_layer + 50_368 * 768 == pytest.approx(149e6, rel=2e-3)
