"""The router's phases (serving/spans.py): counters in ``telemetry()``,
spans on the profiler's host plane, and the ``signal``/``policy`` named
scopes on the compiled route program."""
import glob

import jax
import numpy as np
import pytest

from repro.serving import spans
from repro.serving.router import RouterService, _route_core

DSL = """
SIGNAL embedding math {
  candidates: ["integral derivative algebra equation solve"]
  threshold: 0.5
}
SIGNAL embedding science {
  candidates: ["physics quantum chemistry biology experiment"]
  threshold: 0.5
}
SIGNAL keyword greeting { keywords: ["hello"] }
SIGNAL_GROUP domains {
  semantics: softmax_exclusive
  temperature: 0.1
  threshold: 0.51
  members: [math, science]
  default: science
}
ROUTE greet { PRIORITY 300 WHEN keyword("greeting") MODEL "chat" }
ROUTE math_route { PRIORITY 200 WHEN embedding("math") MODEL "m" }
ROUTE science_route { PRIORITY 100 WHEN embedding("science") MODEL "s" }
GLOBAL { default_model: "s" }
"""

ROUTE_PHASES = ("router.route", "router.embed", "router.pack",
                "router.dispatch", "router.fetch")
BUILD_PHASES = ("router.build.compile", "router.build.validate",
                "router.build.engine", "router.build.tables",
                "router.build.upload")
TEXTS = ["solve the integral", "hello there", "quantum particle energy",
         "derivative of x", "chemistry experiment"]


@pytest.fixture(scope="module")
def svc():
    return RouterService(DSL, load_backends=False)


@pytest.mark.parametrize("audit", [False, True])
def test_route_counters_count_calls_rows_buckets_and_phases(audit):
    svc = RouterService(DSL, load_backends=False, audit=audit)
    assert svc.engine.fused_ok
    for k in (3, 1, 5):
        svc.route(TEXTS[:k])
    svc.route([])                       # no call: nothing is routed
    r = svc.telemetry()["route"]
    assert (r["calls"], r["rows"], r["bucket_rows"]) == (3, 9, 4 + 1 + 8)
    want = ROUTE_PHASES + (("router.observe",) if audit else ())
    assert set(r["phases"]) == set(want)
    for name in want:
        p = r["phases"][name]
        assert p["n"] == 3 and p["seconds"] > 0 and p["over_100ms"] >= 0
    ph = r["phases"]
    inner = sum(ph[n]["seconds"] for n in want if n != "router.route")
    assert inner <= ph["router.route"]["seconds"]


def test_route_counters_only_grow(svc):
    svc.route(TEXTS[:2])
    before = svc.telemetry()["route"]
    svc.route(TEXTS[:4])
    after = svc.telemetry()["route"]
    assert after["calls"] == before["calls"] + 1
    assert after["rows"] == before["rows"] + 4
    assert after["bucket_rows"] == before["bucket_rows"] + 4
    for name, p in before["phases"].items():
        q = after["phases"][name]
        assert q["n"] == p["n"] + 1
        assert q["seconds"] >= p["seconds"]
        assert q["over_100ms"] >= p["over_100ms"]


def test_build_phases_of_the_serving_generation(svc):
    build = svc.telemetry()["build"]
    assert set(build) == set(BUILD_PHASES)
    assert all(s > 0 for s in build.values())
    fresh = RouterService(DSL, load_backends=False, validate=False)
    assert "router.build.validate" not in fresh.telemetry()["build"]


def test_a_phase_counts_its_slow_runs_and_its_failures(monkeypatch):
    c = spans.PhaseCounters()
    with c.phase("a"):
        pass
    monkeypatch.setattr(spans, "SLOW_S", -1.0)
    with pytest.raises(KeyError):
        with c.phase("a"):
            raise KeyError("x")
    snap = c.snapshot()
    assert snap["a"]["n"] == 2 and snap["a"]["over_100ms"] == 1
    assert c.count("a") == 2 and c.count("b") == 0


def test_route_phases_nest_inside_route_on_the_host_plane(svc, tmp_path):
    from jax.profiler import ProfileData
    svc.route(TEXTS[:3])                         # compiled before tracing
    with jax.profiler.trace(str(tmp_path)):
        svc.route(TEXTS[:3])
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    prof = ProfileData.from_file(path[0])
    got = {}
    for plane in prof.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("router."):
                    got.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    assert set(got) == set(ROUTE_PHASES)
    (a, b, meta), = got["router.route"]
    assert (meta["rows"], meta["bucket"]) == (3, 4)
    assert meta["call"] == svc.telemetry()["route"]["calls"] - 1
    for name in ROUTE_PHASES[1:]:
        (x, y, _), = got[name]
        assert a <= x <= y <= b, name


def test_route_program_carries_signal_and_policy_scopes(svc):
    eng, gen = svc.engine, svc._gen
    crisp = eng.crisp_scores(TEXTS[:4])
    text = _route_core.lower(
        np.asarray(eng.embed(TEXTS[:4])), crisp, eng.tensors, gen.jt,
        gen.tables.n_rules, kernel_mode=eng.kernel_mode,
        interpret=eng.interpret, nprobe=eng.nprobe,
        block_b=eng.block_b).compile().as_text()
    assert 'op_name="jit(_route_core)/signal/' in text
    assert 'op_name="jit(_route_core)/policy/' in text
