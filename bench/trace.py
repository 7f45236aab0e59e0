"""Reduction of a profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` the JAX profiler writes, with nothing but
``jax.profiler.ProfileData``:

* device planes (``/device:...``): the ``XLA Modules`` line holds one
  event per program execution (``jit_pool_step(<fingerprint>)``), the
  ``XLA Ops`` line one per operation (``%fusion.3 = <shape> ...``; a
  Pallas kernel appears under its own name).  Busy time is the union of
  the op intervals, averaged over the devices; idle share is one minus
  busy over the traced window.  Each op is charged to the program
  execution that encloses it in time.
* host planes: the benchmark's own ``bench.*`` spans
  (``TraceAnnotation``), which say what the host was doing while the
  device sat idle.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

Interval = Tuple[float, float]

SPAN_PREFIX = "bench."
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                                  # mean over devices
    n_devices: int
    op_s: Dict[str, float]                         # "program:op" -> seconds
    module_s: Dict[str, float]                     # program -> seconds
    module_n: Dict[str, int]                       # module -> executions
    spans: Dict[str, List[Interval]]               # host span -> intervals
    busy: List[Interval]                           # merged, first device
    gaps: List[Tuple[str, float]]                  # idle gaps, longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_time(self, *needles: str) -> Tuple[float, int]:
        """(seconds, executions) of the programs whose name holds any
        of ``needles``."""
        s = n = 0
        for m, t in self.module_s.items():
            if any(k in m for k in needles):
                s += t
                n += self.module_n.get(m, 0)
        return s, n

    def op_time(self, *needles: str) -> float:
        return sum(t for k, t in self.op_s.items()
                   if any(x in k for x in needles))

    def idle_within(self, span: str) -> Tuple[float, float]:
        """(seconds inside ``span`` intervals with no device op running,
        total seconds of those intervals)."""
        total = idle = 0.0
        for a, b in self.spans.get(span, []):
            total += b - a
            idle += (b - a) - _overlap(self.busy, a, b)
        return idle, total


def merge(iv: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _overlap(merged: List[Interval], a: float, b: float) -> float:
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in merged
               if y > a and x < b)


def _label(spans: Dict[str, List[Interval]], t: float) -> str:
    best, width = "outside benchmark spans", float("inf")
    for name, ivs in spans.items():
        for a, b in ivs:
            if a <= t <= b and b - a < width:
                best, width = name, b - a
    return best


def find_xplane(root: str) -> str:
    paths = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return max(paths, key=os.path.getmtime)


def op_name(event_name: str) -> str:
    """``%fusion.3 = bf16[8]{0} fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def program_name(event_name: str) -> str:
    """``jit_pool_step(7812899829063371145)`` -> ``jit_pool_step``."""
    return event_name.split("(", 1)[0]


def reduce(profile, n_gaps: int = 10) -> Summary:
    """``profile``: a ``jax.profiler.ProfileData``."""
    busy_by_dev: Dict[str, List[Interval]] = {}
    op_s: Dict[str, float] = defaultdict(float)
    module_s: Dict[str, float] = defaultdict(float)
    module_n: Dict[str, int] = defaultdict(int)
    spans: Dict[str, List[Interval]] = defaultdict(list)
    lo, hi = float("inf"), float("-inf")
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            mods = sorted((e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9,
                           program_name(e.name))
                          for e in lines.get(MODULE_LINE, []))
            for a, b, name in mods:
                module_s[name] += b - a
                module_n[name] += 1
            starts = [m[0] for m in mods]
            ops = []
            for e in lines.get(OPS_LINE, []):
                a = e.start_ns * 1e-9
                b = a + e.duration_ns * 1e-9
                i = bisect.bisect_right(starts, a) - 1
                prog = mods[i][2] if i >= 0 and a <= mods[i][1] else "?"
                op_s[f"{prog}:{op_name(e.name)}"] += b - a
                ops.append((a, b))
            if ops:
                busy_by_dev[plane.name] = merge(ops)
                lo = min(lo, busy_by_dev[plane.name][0][0])
                hi = max(hi, busy_by_dev[plane.name][-1][1])
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        a = e.start_ns * 1e-9
                        b = a + e.duration_ns * 1e-9
                        spans[e.name].append((a, b))
                        lo, hi = min(lo, a), max(hi, b)
    if not busy_by_dev:
        raise ValueError("the trace holds no device operation")
    busy_s = sum(sum(b - a for a, b in m) for m in busy_by_dev.values()) \
        / len(busy_by_dev)
    first = busy_by_dev[sorted(busy_by_dev)[0]]
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [(_label(spans, (a + b) / 2), b - a) for a, b in gaps[:n_gaps]]
    return Summary(window_s=hi - lo, busy_s=busy_s, n_devices=len(busy_by_dev),
                   op_s=dict(op_s), module_s=dict(module_s),
                   module_n=dict(module_n), spans=dict(spans), busy=first,
                   gaps=labelled)


def load(root: str) -> Summary:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(root)))


def breakdown(s: Summary, n: int = 10) -> Dict[str, list]:
    ops = sorted(s.op_s.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in s.gaps[:n]]}
