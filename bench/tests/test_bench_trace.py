"""The trace reduction on a small recorded-shape fixture.

``fixtures/serve_trace.pbtxt`` is an XSpace in protobuf text form,
written after traces recorded on a v5e with the same planes, lines and
event names (times set by hand): a device plane whose module line holds
one event per program execution, whose op line holds the ops (full HLO
text as the name, a Pallas kernel under its own name) and whose async
line holds copies that overlap compute; and a host plane with the
benchmark's spans among other host events.  Every number below is
worked out by hand from it.
"""
import pathlib

import pytest

from bench import trace

FIX = pathlib.Path(__file__).resolve().parent / "fixtures"


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData
    text = (FIX / "serve_trace.pbtxt").read_text()
    return trace.reduce(ProfileData.from_text_proto(text))


def test_busy_union_and_idle_share(summary):
    # ops [1,2.5], [2.5,3], [3,4], [6,8] ms -> busy 5 ms (the async copy
    # over [0,10] is not compute); the host span [0,10] bounds the window
    assert summary.window_s == pytest.approx(10e-3)
    assert summary.busy_s == pytest.approx(5e-3)
    assert summary.idle_share == pytest.approx(0.5)
    assert summary.n_devices == 1


def test_programs_and_ops_by_name(summary):
    s, n = summary.module_time("pool_step")
    assert (s, n) == (pytest.approx(4e-3), 2)
    s, n = summary.module_time("jit__unknown")
    assert (s, n) == (pytest.approx(1e-3), 1)
    assert summary.op_time("jit_pool_step:fusion.1") == pytest.approx(3.5e-3)
    assert summary.op_time("jit__unknown") == pytest.approx(1e-3)
    assert summary.op_time("ivf_route_candidates") == pytest.approx(0.5e-3)


def test_idle_gaps_are_labelled_by_span(summary):
    # gaps [4,6] and [8,10] fall in bench.route [4.5,9] (the innermost
    # span at their midpoints), [0,1] only in bench.serve_step [0,10]
    assert [(k, round(v * 1e3, 9)) for k, v in summary.gaps] == [
        ("bench.route", 2.0), ("bench.route", 2.0),
        ("bench.serve_step", 1.0)]


def test_host_share_of_a_span(summary):
    # bench.route [4.5, 9]: the device is busy over [6,8] inside it
    idle, total = summary.idle_within("bench.route")
    assert total == pytest.approx(4.5e-3)
    assert idle == pytest.approx(2.5e-3)


def test_breakdown_lists_longest_first(summary):
    b = trace.breakdown(summary)
    assert b["device_ops"][0][0] == "jit_pool_step:fusion.1"
    assert len(b["idle_gaps"]) == 3 and len(b["device_ops"]) <= 10


def test_a_trace_without_device_ops_is_refused():
    from jax.profiler import ProfileData
    text = 'planes { id: 1 name: "/host:CPU" }'
    with pytest.raises(ValueError):
        trace.reduce(ProfileData.from_text_proto(text))
