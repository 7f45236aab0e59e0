"""Shared arithmetic of the metric readers in ``bench/metrics``.

Each reader takes the finished ``Run`` and returns a number, or None
when the run has nothing to read it from (the harness then leaves the
metric out of the line).  A share of a roofline or a peak is never
reported as 0 for want of data: it is left out.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from bench import work

# program names as the trace gives them: the slot scheduler's pooled
# decode step, and a backend's prefill: the chunked step, or the
# single-shot one (jitted through a partial, so XLA names it "_unknown";
# a program-side name is later work)
DECODE = "jit_pool_step"
PREFILL = ("jit_chunk_step", "jit__unknown")


def p95(values) -> Optional[float]:
    v = np.asarray(values, np.float64)
    return float(np.percentile(v, 95)) if v.size else None


def mean_device_ms(run, *needles: str) -> Optional[float]:
    if run.trace is None:
        return None
    s, n = run.trace.module_time(*needles)
    return s / n * 1e3 if n else None


def idle_share(run) -> Optional[float]:
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share


def _traced_steps(run):
    """Decode steps the hook saw while the profiler ran."""
    if run.trace is None or run.hook is None or not run.peaks:
        return []
    a, b = run.trace_span
    return [st for t, st in run.hook.steps if a <= t <= b]


def decode_roofline(run) -> Optional[float]:
    """Least time of the traced decode steps (every weight once plus the
    live KV of their rows, at peak bandwidth) over their device time."""
    steps = _traced_steps(run)
    if not steps:
        return None
    t_dev, n_dev = run.trace.module_time(DECODE)
    if not n_dev:
        return None
    least = 0.0
    count = 0
    for st in steps:
        for name, (rows, live) in st.items():
            shapes = run.backend(name)["shapes"]
            least += work.decode_step_bytes(shapes, rows, live) \
                / run.peaks["hbm_bytes_per_s"]
            count += 1
    if not count:
        return None
    # scale the hook's mean step to the number of steps the trace holds
    return 100.0 * (least / count) * n_dev / t_dev


def serve_mfu(run) -> Optional[float]:
    """Model FLOPs of the prompt and output tokens processed in the
    window, over window x peak."""
    if not run.peaks or run.hook is None:
        return None
    flops = 0.0
    start, end = run.window_span
    for tk, key in run.finished_keys():
        stamps = run.hook.stamps.get(key, [])
        shapes = run.backend(tk.request.backend)["shapes"]
        n_prompt = run.hook.prompt[key]
        if stamps and start <= stamps[0] <= end:
            flops += work.prefill_flops(shapes, n_prompt)
        for j, t in enumerate(stamps):
            if start <= t <= end:
                flops += work.token_flops(shapes, n_prompt + j)
    if not flops:
        return None
    return 100.0 * flops / (run.window_s * run.peaks["bf16_flops"])


def _route_work(run, rows_per_call: float):
    lay = run.layout
    if lay.get("kernel_mode", "").startswith("ivf"):
        return work.ivf_route(rows_per_call, lay)
    return work.flat_route(rows_per_call, lay["n_routes"], lay["dim"],
                           lay["store_bytes"])


# Pallas kernels of each lowering, by the op names the trace gives them
KERNELS = {"fused": ("fused_route",), "fused_dtiled": ("fused_route",),
           "ivf_fused": ("coarse_topk", "ivf_route_candidates")}


def signal_kernel_roofline(run) -> Optional[float]:
    st, lay = run.stats, run.layout
    if run.trace is None or not run.peaks or not st.get("calls"):
        return None
    names = KERNELS.get(lay.get("kernel_mode"))
    if not names:
        return None
    t_k = run.trace.op_time(*names)
    calls = len(run.trace.spans.get("bench.route", []))
    if not t_k or not calls:
        return None
    per_call = work.roofline_s(
        _route_work(run, st["batch_rows"] / st["calls"]), run.peaks)
    return 100.0 * per_call * calls / t_k


def route_mfu(run) -> Optional[float]:
    st, lay = run.stats, run.layout
    if not run.peaks or not st.get("batch_rows") or not lay:
        return None
    flops = work.flat_route(st["batch_rows"], lay["n_routes"], lay["dim"],
                            0)["flops"]
    return 100.0 * flops / (run.window_s * run.peaks["bf16_flops"])


def route_host_share(run) -> Optional[float]:
    if run.trace is None:
        return None
    idle, total = run.trace.idle_within("bench.route")
    return 100.0 * idle / total if total else None
