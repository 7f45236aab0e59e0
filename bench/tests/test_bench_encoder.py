"""The encoder cell (``gte-modernbert-toolbench-16k.agent``) driven
through whole runs on the CPU at ModernBERT's pattern in small (d 64, 4
heads, 6 layers, window 8, vocabulary 512, bf16 as the cell states): a
sound run comes out correct; the fp8 control in the program's place and
an altered embedding come out not correct through the limits; and the
encoder's work counts against a hand count."""
import numpy as np
import pytest

from bench import encoder_work, traffic
from bench import run as bench_run
from bench.builders import load as load_builder
from bench.tests import cells

MANIFEST = cells.manifest()
CELL = "gte-modernbert-toolbench-16k.agent"
SMALL = dict(hidden_size=64, num_hidden_layers=6, num_attention_heads=4,
             intermediate_size=96, vocab_size=512,
             max_position_embeddings=512, local_attention=8)
# at this size the bf16 program reads an embedding gap near 4e-5, a score
# error near 1.4e-3 and no miss; the fp8 control near 8e-3, 2e-2 and a
# few misses: the limits lie between, as the cell's lie between the
# readings at full size on the chip
OVER = {"config": dict(SMALL, n_routes=96, n_topics=4, topic_words=60,
                       limits={"embed_cos_gap_max": 1e-3,
                               "route_miss_share": 0.01,
                               "route_score_mae": 0.01}),
        "traffic": {"rate_per_s": 12.0, "admit_batch": 8,
                    "prompt_bytes": {"median": 64, "sigma": 1.0, "min": 52,
                                     "max": 300}}}


def _run(after=None, **extra):
    hooks = dict(extra)
    hooks.setdefault("control", False)
    if after is not None:
        hooks["after_prepare"] = after
    res = bench_run.run_cell(MANIFEST, CELL, 2**33 + 17, 2.0, False,
                             overrides=OVER, hooks=hooks)
    return res, hooks["run"]


def test_sound_encoder_run_is_correct():
    res, run = _run()
    assert res["correct"] is True, res["checks"]
    assert res["compiles_in_window"] == 0
    num = run.numbers
    assert num["route_miss_share"] == 0.0
    assert 0 < num["embed_cos_gap_max"] < 1e-3
    enc = run.stats["encoder"]
    assert enc["sequences"] == res["attempted"]
    assert enc["tokens"] == sum(it.prompt_bytes
                                for it in run.items[: res["attempted"]])
    rate = bench_run.load_reader("routes_per_s")(run)
    assert rate == res["attempted"] / run.window_s
    pad = bench_run.load_reader("encoder_pad_share.agent")(run)
    assert 0 < pad < 100



def test_route_readers_count_the_encoder_work():
    from bench import peaks
    v5e = peaks.peaks("TPU v5 lite")

    def after(run):
        run.peaks = v5e
    res, run = _run(after)
    read = {m: bench_run.load_reader(m)(run)
            for m in ("route_mfu.flood", "route_mfu.agent",
                      "signal_kernel_roofline.agent",
                      "route_host_share.agent")}
    lengths = [it.prompt_bytes for it in run.items[: res["attempted"]]]
    flops = sum(encoder_work.sequence_flops(run.cfg, n) for n in lengths)
    assert read["route_mfu.agent"] == pytest.approx(
        read["route_mfu.flood"]
        + 100.0 * flops / (run.window_s * v5e["bf16_flops"]))
    assert read["route_mfu.agent"] > read["route_mfu.flood"] > 0
    # the trace's readers have nothing to read in an untraced run
    assert read["signal_kernel_roofline.agent"] is None
    assert read["route_host_share.agent"] is None


def test_cell_builds_the_published_encoder():
    import dataclasses

    from bench.entries import encode_route
    from repro.configs.encoders import MODERNBERT_BASE
    cfg = bench_run.load_json(bench_run.BENCH / "configs"
                              / "gte-modernbert-toolbench-16k.json")
    built = encode_route.encoder_config(cfg)
    assert built == dataclasses.replace(MODERNBERT_BASE, name=cfg["encoder"],
                                        source=built.source)

def test_fp8_control_is_not_correct():
    res, run = _run(control=True)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["embed_cos_gap_max"]["value"] > \
        res["checks"]["embed_cos_gap_max"]["limit"]


def test_altered_embedding_is_not_correct():
    from repro.signals import embedder as emb_mod
    inner = emb_mod._encode_core

    def after(run):
        def altered(*args, **kwargs):       # every written row moved
            slab = inner(*args, **kwargs)
            return slab.at[:, 0].add(0.05)
        emb_mod._encode_core = altered
    try:
        res, run = _run(after)
    finally:
        emb_mod._encode_core = inner
    assert res["correct"] is False
    assert run.numbers["embed_cos_gap_max"] > 1e-3


def test_queries_hold_their_tokens_and_their_route():
    cfg = bench_run.load_json(bench_run.BENCH / "configs"
                              / "gte-modernbert-toolbench-16k.json")
    mix = bench_run.load_json(bench_run.BENCH / "traffic" / "agent.json")
    items = traffic.schedule(mix, 5, 2.0)
    built = load_builder(cfg["builder"]).build(
        cfg, 5, items, np.random.default_rng(5))
    descs = [line.split('candidates: ["')[1].split('"]')[0]
             for line in built.dsl.splitlines()
             if line.startswith("SIGNAL embedding")]
    assert len(descs) == cfg["n_routes"]
    assert all(16 <= len(d.split()) <= 48 for d in descs[:500])
    heads = set(descs)
    for it, t in zip(items, built.texts):
        words = t.split()
        assert len(words) + 2 == it.prompt_bytes
        assert any(" ".join(words[:k]) in heads for k in range(16, 49))


def test_dealt_schedule_keeps_the_mix_and_steadies_the_prefix():
    from bench.entries import encode_route
    mix = bench_run.load_json(bench_run.BENCH / "traffic" / "agent.json")
    deal = encode_route.DEAL
    sums, dealt_sums = [], []
    for seed in (2**31 + 11, 2**31 + 13, 2**31 + 17, 2**31 + 19):
        items = traffic.schedule(mix, seed, 51.0)
        out = encode_route.dealt(items, seed)
        assert [it.due_s for it in out] == [it.due_s for it in items]
        lengths = np.array([it.prompt_bytes for it in out])
        assert sorted(lengths) == sorted(it.prompt_bytes for it in items)
        strata = np.array_split(np.sort(lengths), deal)
        top = [s[-1] for s in strata]
        for b in range(len(out) // deal - 1):       # every block but the
            block = np.sort(lengths[b * deal: (b + 1) * deal])   # last
            # one length from each stratum: the k-th least of the block
            # lies in the k-th stratum
            assert all(s[0] <= n <= t for s, n, t in zip(strata, block, top))
        assert set(strata[-1]) == {mix["prompt_bytes"]["max"]}
        # the longest prompts' share of a stopped window's prefix
        cut = int(0.85 * len(items))
        sq = lambda ls: float(np.sum(np.square(ls[:cut], dtype=float)))  # noqa: E731
        sums.append(sq(np.array([it.prompt_bytes for it in items])))
        dealt_sums.append(sq(lengths))
    assert np.ptp(dealt_sums) < 0.25 * np.ptp(sums)


# ModernBERT's pattern at d 8, ffn 12, 4 layers: every 3rd global
SMALL_WORK = dict(hidden_size=8, num_hidden_layers=4, intermediate_size=12,
                  global_attn_every_n_layers=3, local_attention=4)


def test_encoder_work_counts():
    # per layer: q, k, v 3*8*8 = 192, o 64, Wi 8*24 = 192, Wo 12*8 = 96
    assert encoder_work.matmul_params(SMALL_WORK) == 4 * 544
    assert encoder_work.layer_counts(SMALL_WORK) == {"global": 2,
                                                     "local": 2}
    # n = 10: 2*2176*10 matmul; global 4*8*10*10 twice; local keys
    # min(10, 5): 4*8*10*5 twice
    assert encoder_work.sequence_flops(SMALL_WORK, 10) == \
        43520 + 6400 + 3200
    cfg = bench_run.load_json(bench_run.BENCH / "configs"
                              / "gte-modernbert-toolbench-16k.json")
    assert encoder_work.matmul_params(cfg) == 110_297_088
    assert encoder_work.layer_counts(cfg) == {"global": 8, "local": 14}
    # the 1,024-token row of the configuration's table: 226 GFLOP of
    # matrix work, 26 of global and 6 of local attention
    f = encoder_work.sequence_flops(cfg, 1024)
    assert f == pytest.approx(226e9 + 25.8e9 + 5.7e9, rel=2e-3)
    peak = {"bf16_flops": 197e12}
    assert encoder_work.least_s(cfg, [1024, 1024], peak) == \
        pytest.approx(2 * f / 197e12)
