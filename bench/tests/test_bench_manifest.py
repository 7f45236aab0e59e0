"""BENCHMARK.json against the benchmark's contract, and a throwaway
configuration, mix and metric found by name from another directory."""
import json
import pathlib
import re

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_shape():
    assert set(MANIFEST) == KEYS
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    assert MANIFEST["paths"] == ["bench"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) <= 64 * 1024
    # a full check of 24 cells fits its time
    runs = 2 + 14 * 24
    assert runs * (MANIFEST["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_lines():
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    for key in ("end_to_end", "per_layer"):
        for m in MANIFEST[key]:
            assert NAME.match(m["name"]) and UNIT.match(m["unit"])
            assert m["better"] in ("lower", "higher")
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                         "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"])
    names = [m["name"] for k in ("end_to_end", "per_layer")
             for m in MANIFEST[k]]
    assert len(names) == len(set(names))
    assert len(CELLS) == len(MANIFEST["workloads"])
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}


def test_every_cell_has_its_files_and_metrics():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    for name, w in CELLS.items():
        assert w["config"] in configs
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        e2e = [m["name"] for m in MANIFEST["end_to_end"]
               if name in m.get("workloads", [name])]
        assert "setup_s" in e2e and len(e2e) >= 2, name
        assert any(name in m.get("workloads", [name])
                   for m in MANIFEST["per_layer"]), name
    for c in configs.values():
        assert any(w["config"] == c["name"] for w in CELLS.values())
    for key in ("end_to_end", "per_layer"):
        for m in MANIFEST[key]:
            assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m
            assert all(w in CELLS for w in m.get("workloads", []))


def test_moves_is_reported_where_the_metric_is():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", list(CELLS)):
            assert cell in moved.get("workloads", [cell]), (m["name"], cell)


def test_a_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A later PR adds a cell with files alone: here a configuration, a
    mix and a reader in another directory, no existing file edited."""
    from bench import run as bench_run
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    cfg = json.loads((BENCH / "configs" / "toolbench-16k.json").read_text())
    # a throwaway table at a test's size, with a limit for that size
    cfg.update(n_routes=128, dim=64, n_topics=4,
               limits={"route_miss_share": 0.0, "route_score_mae": 1e-4})
    (tmp_path / "configs" / "tiny-tools.json").write_text(json.dumps(cfg))
    (tmp_path / "traffic" / "trickle.json").write_text(json.dumps({
        "entry": "route", "rate_per_s": 40.0,
        "admit_batch": 4,
        "prompt_bytes": {"median": 1, "sigma": 0.0, "min": 1, "max": 1},
        "tenants": [{"name": "agent", "share": 1.0}]}))
    (tmp_path / "metrics" / "route_calls.py").write_text(
        "def read(run):\n    return run.stats['calls']\n")
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"].append({"name": "tiny-tools", "source": "test",
                                "file": "x", "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": "tiny-tools.trickle",
                                  "config": "tiny-tools",
                                  "traffic": "trickle", "chips": 1,
                                  "why": "test"})
    manifest["end_to_end"].append({"name": "route_calls", "unit": "1",
                                   "better": "lower", "bound": 0.1,
                                   "source": "host_clock",
                                   "workloads": ["tiny-tools.trickle"]})
    res = bench_run.run_cell(manifest, "tiny-tools.trickle", 2**33 + 1, 1.0,
                             False, extra_roots=[tmp_path])
    assert res["correct"] is True
    assert res["attempted"] == 40
    assert res["metrics"]["route_calls"]["value"] > 0
    assert set(res["metrics"]) == {"route_calls", "setup_s"}
    assert list(res)[-1] == "checks"
