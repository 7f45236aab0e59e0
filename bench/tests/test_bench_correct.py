"""The comparison that decides ``correct``, driven through whole runs on
the CPU at a size a test run holds: the harness's look for a chip is
skipped, everything after it runs.  A sound run comes out correct; the
lower-precision control in the program's place, and the timed path
broken underneath, come out not correct, each through the cell's own
limits."""
import json
import pathlib

import pytest

from bench import run as bench_run
from bench.tests import cells

BENCH = pathlib.Path(__file__).resolve().parents[1]
MANIFEST = cells.manifest()
HOOKS = {"tail_s": 20.0, "drain_s": 5.0}


def _smoke_shapes(arch):
    from repro.configs.registry import get_config
    c = get_config(arch, smoke=True)
    return dict(n_layers=c.n_layers, d_model=c.d_model, n_heads=c.n_heads,
                n_kv_heads=c.n_kv_heads, head_dim=c.resolved_head_dim,
                d_ff=c.d_ff, vocab_size=c.vocab_size, norm=c.norm,
                norm_eps=c.norm_eps, rope_theta=c.rope_theta,
                partial_rotary=c.partial_rotary, dtype=c.dtype)


def _serve_overrides():
    cfg = json.loads((BENCH / "configs" / "llm-routing.json").read_text())
    backends = [dict(b, size="smoke", shapes=_smoke_shapes(b["arch"]))
                for b in cfg["backends"]]
    # where the configuration has no limit for the mean gap, this size
    # takes one between its own readings: the float32 smoke backends
    # read 0 and the fp8 control 2e-3 to 3e-3
    limits = dict({"logit_gap_mean": 1e-3}, **cfg["limits"])
    return {"config": {"backends": backends, "max_seq": 128, "slots": 3,
                       "limits": limits},
            "traffic": {"rate_per_s": 5.0,
                        "prompt_bytes": {"min": 32, "max": 64},
                        "output_tokens": {"median": 8, "max": 16}}}


ROUTE = {"config": {"n_routes": 512, "n_topics": 8},
         "traffic": {"rate_per_s": 60.0}}


def _run(cell, over, after=None, seed=2**33 + 17, seconds=2.0, **extra):
    hooks = dict(HOOKS, **extra)
    if after is not None:
        hooks["after_prepare"] = after
    res = bench_run.run_cell(MANIFEST, cell, seed, seconds, False,
                             overrides=over, hooks=hooks)
    return res, hooks["run"].numbers


def test_sound_serve_run_is_correct():
    res, num = _run("llm-routing.chat", _serve_overrides())
    assert res["correct"] is True, res["checks"]
    assert num["served_tokens_checked"] > 0
    # the float32 smoke backends match the float32 reference to rounding
    assert num["logit_gap"] < 1e-3


# the routing control errs by the contest among many routes: at 512 it
# reads within two times of the program, at 4,096 near four times, so
# this size carries a limit between the two, as the cell's own limit
# lies between the readings at 16,464 routes on the chip
ROUTE_CONTROL = {"config": {"n_routes": 4096, "n_topics": 49,
                            "limits": {"route_score_mae": 3e-7,
                                       "route_miss_share": 0.01}},
                 "traffic": {"rate_per_s": 100.0}}


@pytest.mark.parametrize("cell", ["llm-routing.chat", "toolbench-16k.lookup"])
def test_lower_precision_control_is_not_correct(cell):
    over = _serve_overrides() if cell == "llm-routing.chat" \
        else ROUTE_CONTROL
    res, num = _run(cell, over, control=True)
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_sound_route_run_is_correct():
    res, num = _run("toolbench-16k.lookup", ROUTE)
    assert res["correct"] is True, res["checks"]
    assert num["route_miss_share"] == 0.0
    assert num["route_score_mae"] < res["checks"]["route_score_mae"]["limit"]


def test_route_window_that_stops_at_its_time_counts_what_it_routed():
    """Offered far above what the loop routes, a ``"close": "stop"`` mix
    ends at ``--seconds``: the rate counts only the routed requests, over
    the window up to the last call's return, and they come out correct."""
    from bench.run import load_reader
    over = {"config": ROUTE["config"],
            "traffic": {"rate_per_s": 20000.0, "admit_batch": 2,
                        "close": "stop"}}
    hooks = dict(HOOKS)
    res = bench_run.run_cell(MANIFEST, "toolbench-16k.flood", 2**33 + 5,
                             1.0, False, overrides=over, hooks=hooks)
    run = hooks["run"]
    assert res["correct"] is True, res["checks"]
    assert 0 < res["attempted"] < len(run.items)
    assert run.stats["route_ms"].size == res["attempted"]
    assert 1.0 <= run.window_s < 2.0
    rate = load_reader("routes_per_s")(run)
    assert rate == res["attempted"] / run.window_s


def _shift_routes(run):
    """Fault: every route answer altered where it is produced."""
    from repro.serving import router
    inner = router._route_core

    def altered(*args, **kwargs):
        idx, score, norm, fired = inner(*args, **kwargs)
        n_rules = args[4]
        return (idx + 1) % (n_rules + 1), score, norm, fired

    run.restore = lambda: setattr(router, "_route_core", inner)
    router._route_core = altered


def test_altered_route_answers_are_not_correct():
    run_box = {}

    def after(run):
        _shift_routes(run)
        run_box["run"] = run
    try:
        res, num = _run("toolbench-16k.lookup", ROUTE, after)
    finally:
        run_box["run"].restore()
    assert res["correct"] is False
    assert num["route_miss_share"] > 0.5


def _break_decode(kind):
    def after(run):
        for pool in run.svc.scheduler.pools.values():
            inner = pool._pool_step
            vocab = pool.rt.model.cfg.vocab_size

            def step(params, cache, tok, pos, active, _inner=inner,
                     _vocab=vocab):
                nxt, new = _inner(params, cache, tok, pos, active)
                if kind == "token":      # a token altered where produced
                    return (nxt + 1) % _vocab, new
                return nxt, cache        # the step returns its state unchanged

            pool._pool_step = step
    return after


@pytest.mark.parametrize("kind", ["token", "state"])
def test_broken_decode_is_not_correct(kind):
    res, num = _run("llm-routing.chat", _serve_overrides(),
                    _break_decode(kind))
    assert res["correct"] is False
    assert num["logit_gap"] > res["checks"]["logit_gap"]["limit"]
