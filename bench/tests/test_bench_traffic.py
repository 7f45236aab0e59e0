"""The seeded generator: determinism, steadiness across seeds, and
lengths that fit every cell's configuration."""
import json
import pathlib

import numpy as np
import pytest

from bench import traffic
from bench.builders import load as load_builder
from bench.tests import cells

BENCH = pathlib.Path(__file__).resolve().parents[1]
MANIFEST = cells.manifest()


def _cell_files(cell):
    cfg = json.loads((BENCH / "configs" / f"{cell['config']}.json").read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return cfg, mix


def _texts(cell, seed, seconds=4.0):
    cfg, mix = _cell_files(cell)
    if cfg["builder"] == "tool_table":
        cfg = dict(cfg, n_routes=256, n_topics=8)
    items = traffic.schedule(mix, seed, seconds)
    built = load_builder(cfg["builder"]).build(
        cfg, seed, items, np.random.default_rng(seed))
    return items, built.texts


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=[w["name"] for w in MANIFEST["workloads"]])
def test_same_seed_same_fingerprint(cell):
    seed = 4_000_000_007
    a = traffic.fingerprint(*_texts(cell, seed))
    b = traffic.fingerprint(*_texts(cell, seed))
    c = traffic.fingerprint(*_texts(cell, seed + 1))
    assert a == b
    assert a != c


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=[w["name"] for w in MANIFEST["workloads"]])
def test_every_seed_gets_the_same_sizes(cell):
    _, mix = _cell_files(cell)
    runs = [traffic.schedule(mix, s, 8.0) for s in (1, 2**31 + 5, 987654321)]
    for field in ("prompt_bytes", "output_tokens", "tenant"):
        multisets = [sorted(getattr(it, field) for it in r) for r in runs]
        assert multisets[0] == multisets[1] == multisets[2]
    for r in runs:
        due = [it.due_s for it in r]
        assert due == sorted(due)
        assert 0 < due[0] and due[-1] == pytest.approx(8.0)


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=[w["name"] for w in MANIFEST["workloads"]])
def test_lengths_fit_the_configuration(cell):
    cfg, mix = _cell_files(cell)
    if mix["entry"] != "serve":
        return
    max_seq = cfg["max_seq"]
    pb, ot = mix["prompt_bytes"], mix["output_tokens"]
    assert pb["max"] <= max_seq // 2
    assert pb["max"] + ot["max"] <= max_seq
    # the single-shot prefill left-pads a batch to a power of two without
    # a mask, and the reference runs each prompt unpadded: so every prompt
    # takes the chunked path or is exactly the chunk, a power of two
    chunk = cfg["prefill_chunk"]
    assert chunk & (chunk - 1) == 0 and pb["min"] >= chunk
    items, texts = _texts(cell, 77)
    for it, t in zip(items, texts):
        assert len(t.encode()) == it.prompt_bytes <= max_seq // 2
        assert it.output_tokens <= ot["max"]


def test_quantiles_follow_the_distribution():
    spec = {"median": 256, "sigma": 0.7, "min": 32, "max": 512}
    x = traffic.quantile_lengths(spec, 1001)
    assert int(np.median(x)) == 256
    assert x.min() >= 32 and x.max() <= 512
    g = traffic.quantile_gaps(3.0, 10.0, 30)
    assert g.sum() == pytest.approx(10.0)
    assert traffic.tenant_list([{"name": "a", "share": 0.45},
                                {"name": "b", "share": 0.45},
                                {"name": "c", "share": 0.1}], 31).count("c") == 3
