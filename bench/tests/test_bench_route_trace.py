"""The program's router.* spans leave the trace reduction's numbers as
they were.

``fixtures/route_trace.pbtxt`` is a flood-cell trace in miniature: two
``jit__route_core`` calls whose ops carry their named scope as a stat,
and on the host the benchmark's ``bench.route`` span with the router's
own ``router.*`` phases nested inside it.  The reduction reads
``bench.*`` spans only, so the same trace with the ``router.*`` events
taken out reduces to the same summary, and the readers of the flood
cell read the same numbers from both.  Every number below is worked
out by hand from the fixture.
"""
import pathlib
import re
import types

import pytest

from bench import peaks, run, trace

FIX = pathlib.Path(__file__).resolve().parent / "fixtures"
# host events whose metadata id is 10-14: the router.* phases
ROUTER_EVENTS = re.compile(r"^\s*events \{ metadata_id: 1[0-4] .*\n", re.M)
FLOOD_READERS = ("device_idle_share.flood", "route_host_share.flood",
                 "signal_kernel_roofline.flood", "route_mfu.flood")


def _reduce(text):
    from jax.profiler import ProfileData
    return trace.reduce(ProfileData.from_text_proto(text))


@pytest.fixture(scope="module")
def text():
    return (FIX / "route_trace.pbtxt").read_text()


@pytest.fixture(scope="module")
def summary(text):
    return _reduce(text)


@pytest.fixture(scope="module")
def without_router(text):
    stripped = ROUTER_EVENTS.sub("", text)
    assert "router." in stripped and stripped.count("events {") == \
        text.count("events {") - 10
    return _reduce(stripped)


def test_router_spans_leave_the_summary_as_it_was(summary, without_router):
    assert summary == without_router


def test_window_busy_and_host_share(summary):
    # window from bench.route's start (0.2 ms) to its end (9.6 ms); ops
    # [1,4] and [6,9] ms busy
    assert summary.window_s == pytest.approx(9.4e-3)
    assert summary.busy_s == pytest.approx(6e-3)
    assert summary.idle_share == pytest.approx(1 - 6 / 9.4)
    # bench.route [0.2,4.6] and [4.8,9.6]: 9.2 ms, 3 ms of each busy
    idle, total = summary.idle_within("bench.route")
    assert (idle, total) == (pytest.approx(3.2e-3), pytest.approx(9.2e-3))
    assert "router.route" not in summary.spans


def test_gaps_keep_their_benchmark_labels(summary):
    # [4,6] ms centres on 5.0, inside bench.embed [4.95,5.35]; [0.2,1]
    # and [9,9.6] in bench.route
    assert [(k, round(v * 1e3, 9)) for k, v in summary.gaps] == [
        ("bench.embed", 2.0), ("bench.route", 0.8), ("bench.route", 0.6)]


def test_program_and_op_times(summary):
    assert summary.module_time("jit__route_core") == (
        pytest.approx(6e-3), 2)
    assert summary.op_time("ivf_route_candidates") == pytest.approx(1e-3)
    assert summary.op_time("select_reduce_fusion", "reduce_sum") == \
        pytest.approx(5e-3)


def _run(summary):
    layout = {"kernel_mode": "ivf_fused", "n_routes": 16464, "dim": 384,
              "block_b": 128, "nprobe": 14, "n_slabs": 128, "slab_k": 256,
              "slab_row_bytes": 1536, "store_bytes": 16464 * 384 * 4}
    return types.SimpleNamespace(
        trace=summary, stats={"calls": 2, "batch_rows": 32}, layout=layout,
        peaks=peaks.peaks("TPU v5 lite"), window_s=9.4e-3)


@pytest.mark.parametrize("metric", FLOOD_READERS)
def test_flood_readers_read_the_same_with_router_spans(metric, summary,
                                                       without_router):
    read = run.load_reader(metric)
    got = read(_run(summary))
    assert got is not None and got == read(_run(without_router))
    want = {"device_idle_share.flood": 100 * (1 - 6 / 9.4),
            "route_host_share.flood": 100 * 3.2 / 9.2}
    if metric in want:
        assert got == pytest.approx(want[metric])
