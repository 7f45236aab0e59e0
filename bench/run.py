"""One benchmark run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``) in ``BENCHMARK.json``; the
mix's ``entry`` picks the load module (``bench/entries/<entry>.py``) and each
metric is read by ``bench/metrics/<metric>.py``.  A run loads and warms
everything the cell's traffic uses (set-up), measures for ``--seconds``,
checks what the timed path produced against the plain reference, and
prints one JSON line last on stdout.  With ``--trace 1`` the profiler
records a short steady part of the window and the line carries the
per-layer metrics instead of the end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse        # noqa: E402
import gc              # noqa: E402
import importlib.util  # noqa: E402
import json            # noqa: E402
import pathlib         # noqa: E402
import shutil          # noqa: E402
import sys             # noqa: E402
import tempfile        # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

CACHE_DIR = ROOT / ".jax_cache"
TRACE_S = 4.0          # length of the traced part of a --trace 1 window
ROUTE_SAMPLE = 2048    # routed requests compared with the reference
DECODE_TOKENS = 256    # served tokens compared per backend, at least
TAIL_S = 60.0          # wait past the window for late first tokens
DRAIN_S = 2.0          # then let in-flight requests finish this long


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def merged(base: Dict[str, Any], over: Optional[Dict[str, Any]]):
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def find(kind: str, name: str, roots: List[pathlib.Path]) -> pathlib.Path:
    """``<root>/<kind>/<name>`` in the first root that has it: the
    benchmark finds every configuration, mix and reader by name."""
    for r in roots:
        if (r / kind / name).is_file():
            return r / kind / name
    raise FileNotFoundError(f"no {kind}/{name} under {[str(r) for r in roots]}")


def load_reader(name: str, roots: List[pathlib.Path] = (BENCH,)):
    path = find("metrics", f"{name}.py", list(roots))
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Tracer:
    """Profiles ``TRACE_S`` seconds in the middle of the window."""

    def __init__(self, on: bool, seconds: float, length: float):
        self.on = on
        self.at = max(0.0, (seconds - length) / 2)
        self.length = min(length, seconds)
        self.dir: Optional[str] = None
        self.state = "idle"
        self.t_on = self.t_off = None     # elapsed seconds at start/stop

    def poll(self, elapsed: float) -> None:
        if not self.on:
            return
        import jax
        if self.state == "idle" and elapsed >= self.at:
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # spans only, no per-call tracing
            t = time.perf_counter()
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            # the trace holds what comes after the start returned
            self.t_on = elapsed + (time.perf_counter() - t)
            self.state = "tracing"
        elif self.state == "tracing" and elapsed >= self.at + self.length:
            jax.profiler.stop_trace()
            self.t_off = elapsed
            self.state = "done"

    def finish(self, elapsed: float) -> None:
        if self.state == "tracing":
            import jax
            jax.profiler.stop_trace()
            self.t_off = elapsed
            self.state = "done"

    def reduce(self):
        if self.dir is None:
            return None
        from bench import trace
        try:
            return trace.load(self.dir)
        except ValueError as e:        # a trace with no device operation
            say(f"trace not reduced: {e}")
            return None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class Run:
    """Everything one run knows; the metric readers read it."""

    def __init__(self, cell: Dict[str, Any], cfg: Dict[str, Any],
                 mix: Dict[str, Any], seed: int, seconds: float,
                 traced: bool):
        from bench import traffic
        from bench.builders import load as load_builder
        import numpy as np
        self.cell, self.cfg, self.mix = cell, cfg, mix
        self.seed, self.seconds, self.spans = seed, seconds, traced
        self.items = traffic.schedule(mix, seed, seconds)
        self.built = load_builder(cfg["builder"]).build(
            cfg, seed, self.items, np.random.default_rng(seed))
        self.stats: Dict[str, Any] = {}
        self.trace = None
        self.layout: Dict[str, Any] = {}
        self.window_s = float(seconds)
        self.setup_s = 0.0
        self.peaks: Dict[str, float] = {}
        self.sample, self.decode_tokens = ROUTE_SAMPLE, DECODE_TOKENS
        self.tail_s, self.drain_s = TAIL_S, DRAIN_S
        self.control = False      # the control in the program's place
        self.svc = self.front = self.recorder = self.hook = None
        self.tickets: List[Any] = []
        self.window_span = self.trace_span = (0.0, 0.0)

    def embedder(self):
        if self.built.embedder is not None:
            return self.built.embedder
        from repro.signals.embedder import HashEmbedder
        return HashEmbedder(dim=self.built.embedder_dim)

    def warm_items(self, n: int, nbytes: Optional[int] = None):
        """``n`` set-up requests of the mix's first tenant (or of every
        tenant in turn), ``nbytes`` long (the mix's median otherwise)."""
        from bench import traffic
        tenants = [t["name"] for t in self.mix["tenants"]]
        size = nbytes or int(self.mix["prompt_bytes"]["median"])
        return [traffic.Item(i, 0.0, tenants[i % len(tenants)], size, 2)
                for i in range(n)]

    def finished_keys(self):
        """(ticket, hook key) of every request that produced tokens."""
        for tk in self.tickets:
            if tk is not None and tk.request is not None \
                    and id(tk) in self.hook.stamps:
                yield tk, id(tk)

    def backend(self, name: str) -> Dict[str, Any]:
        return next(b for b in self.built.backends if b["name"] == name)

    def release(self) -> None:
        """Free the program's device state before the reference runs."""
        if self.front is not None:
            self.front.drain(timeout_s=1.0)
        self.svc = self.front = None
        if self.recorder is not None:
            self.recorder.svc = None
        gc.collect()


def report_metrics(run: Run, names: List[str],
                   roots: List[pathlib.Path]) -> Dict[str, Any]:
    out = {}
    for m in names:
        val = load_reader(m["name"], roots)(run)
        if val is not None:
            out[m["name"]] = {"value": float(val), "unit": m["unit"]}
    return out


def cell_metrics(manifest: Dict[str, Any], cell: str, key: str):
    return [m for m in manifest[key]
            if cell in m.get("workloads", [cell])]


def run_cell(manifest: Dict[str, Any], workload: str, seed: int,
             seconds: float, traced: bool,
             overrides: Optional[Dict[str, Any]] = None,
             hooks: Optional[Dict[str, Any]] = None,
             extra_roots: Optional[List[pathlib.Path]] = None
             ) -> Dict[str, Any]:
    """A whole run after the device check.  ``overrides`` merge into the
    configuration and mix files ({"config": ..., "traffic": ...});
    ``hooks`` let a test break the timed path ({"after_prepare": f});
    ``extra_roots`` are searched before ``bench/`` for configurations,
    mixes and readers."""
    import jax
    import numpy as np
    from bench import peaks as peaks_mod
    from bench.clock import CompileClock, peak_bytes
    hooks = hooks or {}
    cell = next(w for w in manifest["workloads"] if w["name"] == workload)
    roots = list(extra_roots or []) + [BENCH]
    cfg = merged(load_json(find("configs", f"{cell['config']}.json", roots)),
                 (overrides or {}).get("config"))
    mix = merged(load_json(find("traffic", f"{cell['traffic']}.json", roots)),
                 (overrides or {}).get("traffic"))
    clock = CompileClock()
    dev = jax.devices()[0]
    entry = importlib.import_module(f"bench.entries.{mix['entry']}")
    run = Run(cell, cfg, mix, seed, seconds, traced)
    hooks["run"] = run
    run.device = dev
    for k in ("sample", "decode_tokens", "tail_s", "drain_s", "control"):
        if k in hooks:
            setattr(run, k, hooks[k])
    if dev.platform == "tpu":
        run.peaks = peaks_mod.peaks(dev.device_kind)
    entry.prepare(run)
    if "after_prepare" in hooks:
        hooks["after_prepare"](run)
    # what set-up built lives for the whole run: keep it out of the
    # collector, whose full passes over it pause the timed loop
    gc.collect()
    gc.freeze()
    run.setup_s = time.perf_counter() - _T0
    setup_compiles = (clock.count, clock.seconds)
    clock.reset()
    tracer = Tracer(traced, seconds, TRACE_S)
    pauses: List[float] = []
    gc_start = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_start[0] = time.perf_counter()
        else:
            pauses.append(time.perf_counter() - gc_start[0])
    gc.callbacks.append(on_gc)
    try:
        entry.window(run, tracer)
    finally:
        gc.callbacks.remove(on_gc)
        gc.unfreeze()
    in_window = (clock.count, clock.seconds)
    peak = peak_bytes()
    run.trace = tracer.reduce()
    st = run.stats
    late = np.asarray(st.get("lateness_ms", []), np.float64)
    cached = sorted(CACHE_DIR.glob("*")) if CACHE_DIR.is_dir() else []
    say(f"compile cache {CACHE_DIR}: {len(cached)} files, "
        f"{sum(f.stat().st_size for f in cached) / 2**20:.1f} MiB")
    say(f"set-up {run.setup_s:.3f} s with {setup_compiles[0]} compiles "
        f"({setup_compiles[1]:.3f} s); window {run.window_s:.3f} s with "
        f"{in_window[0]} compiles ({in_window[1]:.3f} s)")
    if late.size:
        say(f"generator lateness: p50 {np.percentile(late, 50):.3f} ms, "
            f"p95 {np.percentile(late, 95):.3f} ms, max {late.max():.3f} "
            f"ms over {late.size} requests")
    say(f"collector in the window: {len(pauses)} passes, longest "
        f"{max(pauses, default=0.0) * 1e3:.3f} ms")
    if len(st.get("call_ms", [])):
        ms, at = st["call_ms"], st["call_at_s"]
        q = np.percentile(ms, [50, 95, 99])
        say(f"route calls: {ms.size}, {np.mean(st['call_rows']):.2f} rows "
            f"each; p50 {q[0]:.3f} ms, p95 {q[1]:.3f} ms, p99 {q[2]:.3f} "
            f"ms; {int((ms > 100).sum())} over 100 ms")
        for i in np.argsort(-ms)[:3]:
            say(f"long route call {ms[i]:.3f} ms, {at[i]:.3f} s into the "
                f"window")
        gap = (at[1:] - at[:-1] - ms[:-1] / 1e3) * 1e3
        if gap.size:
            say(f"loop between calls: p50 {np.percentile(gap, 50):.3f} "
                f"ms, max {gap.max():.3f} ms")
    t_ref = time.perf_counter()
    numbers = entry.verify(run)
    say(f"reference check took {time.perf_counter() - t_ref:.3f} s; "
        + ", ".join(f"{k} {v!r}" for k, v in sorted(numbers.items())))
    run.numbers = numbers
    limits = cfg["limits"]
    checks = {k: {"value": float(numbers[k]), "limit": float(limits[k])}
              for k in sorted(limits) if k in numbers}
    ok = (bool(checks)
          and all(c["value"] <= c["limit"] for c in checks.values())
          and st.get("attempted", 0) > 0 and st.get("failed", 1) == 0
          and st.get("hook_errors", 0) == 0
          and st.get("crashed_steps", 0) == 0)
    key = "per_layer" if traced else "end_to_end"
    result: Dict[str, Any] = {
        "correct": bool(ok), "attempted": int(st.get("attempted", 0)),
        "failed": int(st.get("failed", 0)),
        "metrics": report_metrics(run, cell_metrics(manifest, workload, key),
                                  roots),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak},
        "compiles_in_window": in_window[0],
    }
    if traced and run.trace is not None:
        from bench import trace as trace_mod
        result["device"]["busy_s"] = run.trace.busy_s
        result["device"]["window_s"] = run.trace.window_s
        result["breakdown"] = trace_mod.breakdown(run.trace)
    for k in ("hook_errors", "crashed_steps"):
        if k in st:
            checks[k] = {"value": float(st[k]), "limit": 0.0}
    checks["failed"] = {"value": float(st.get("failed", 0)), "limit": 0.0}
    for name, c in checks.items():
        say(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    manifest = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        print(f"bench: no workload {args.workload!r}; known: "
              f"{sorted(cells)}", file=sys.stderr)
        return 2
    import jax
    # a fixed directory inside the checkout, every program cached with no
    # size limit, set before JAX starts its backend: so only a cell's
    # first run in a checkout compiles, whatever the environment names
    for key, val in (("jax_enable_compilation_cache", True),
                     ("jax_compilation_cache_dir", str(CACHE_DIR)),
                     ("jax_compilation_cache_max_size", -1),
                     ("jax_persistent_cache_min_compile_time_secs", 0.0),
                     ("jax_persistent_cache_min_entry_size_bytes", 0)):
        jax.config.update(key, val)
    devices = jax.devices()
    want = cells[args.workload]["chips"]
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"bench: {args.workload} needs {want} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 2
    result = run_cell(manifest, args.workload, args.seed, args.seconds,
                      bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
