"""The cells the tests drive: every cell of ``BENCHMARK.json``, plus the
chat and lookup cells, whose files are kept and tested while the
manifest does not run them on the chip."""
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]
KEPT = [{"name": "llm-routing.chat", "config": "llm-routing",
         "traffic": "chat", "chips": 1, "why": "chat users"},
        {"name": "toolbench-16k.lookup", "config": "toolbench-16k",
         "traffic": "lookup", "chips": 1, "why": "agents, one tool a call"}]


def manifest():
    """``BENCHMARK.json`` with the kept cells in it."""
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = {w["name"] for w in m["workloads"]}
    m["workloads"] += [w for w in KEPT if w["name"] not in names]
    return m
