"""What the benchmark reads from the program while it runs.

Everything here wraps a method on one service *instance* and leaves the
computation alone: it records what the timed path returned and, in a
traced run, opens a ``TraceAnnotation`` span around the call so the
trace reduction can tell which layer the host was in.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np


def _span(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def wrap(obj: Any, method: str, span: Optional[str], after=None) -> None:
    """Replace ``obj.method`` by a call through a span (when ``span``)
    that hands its arguments and result to ``after``."""
    inner = getattr(obj, method)

    def call(*args, **kwargs):
        with _span(span is not None, span or ""):
            out = inner(*args, **kwargs)
        if after is not None:
            after(args, kwargs, out)
        return out

    setattr(obj, method, call)


class RouteRecorder:
    """Records (route index, score) per routed text from
    ``RouterService._route_eval``, which ``route`` and ``enqueue`` both
    call once per batch."""

    def __init__(self, svc, spans: bool):
        self.svc = svc
        self.by_text: Dict[str, Tuple[int, float]] = {}
        self.decisions: Dict[int, Tuple[str, float]] = {}
        self._last: Optional[np.ndarray] = None
        wrap(svc, "_route_eval", "bench.route" if spans else None,
             self._record)
        emb = svc.engine.embedder
        if spans:
            wrap(emb, "embed", "bench.embed")

    def _record(self, args, kwargs, out) -> None:
        texts = args[0]
        idx, score = out
        self._last = score
        for t, i, s in zip(texts, idx, score):
            self.by_text[t] = (int(i), float(s))

    def route(self, texts: Sequence[str], rows: Sequence[int]) -> List[str]:
        names = self.svc.route(texts)
        for r, n, s in zip(rows, names, self._last):
            self.decisions[r] = (n, float(s))
        return names


def route_layout(svc) -> Dict[str, Any]:
    """The bound signal layer's shapes, for the work counts."""
    eng = svc.engine
    t = eng.tensors
    out = {"kernel_mode": eng.kernel_mode, "n_routes": int(
        t["centroids"].shape[0]), "dim": int(eng._embed_dim),
        "block_b": int(eng.block_b), "nprobe": int(eng.nprobe),
        "n_slabs": int(eng.n_slabs),
        "store_bytes": int(t["centroids"].size
                           * t["centroids"].dtype.itemsize)}
    if "ivf_heads" in t:
        s = int(t["ivf_heads"].shape[0])
        out["slab_k"] = int(t["ivf_store"].shape[0]) // s
        out["slab_row_bytes"] = int(t["ivf_store"].shape[1]
                                    * t["ivf_store"].dtype.itemsize)
    return out


class TokenClock:
    """The serving-thread hook: stamps, after every serve step, each
    in-flight request's token count as it grows, and the live KV
    positions each backend's decode step read.  Errors are counted
    here, since the ingress swallows what an ``on_step`` hook raises."""

    def __init__(self, clock):
        self.clock = clock
        self.new: List[Any] = []          # tickets handed over by clients
        self.prompt: Dict[int, int] = {}  # prompt tokens per ticket
        self.lock = threading.Lock()
        self.live: List[Any] = []
        self.seen: Dict[int, int] = {}
        self.first: Dict[int, float] = {}
        self.stamps: Dict[int, List[float]] = {}
        self.errors = 0
        # per serve step: (time, {backend: (rows decoded, live KV
        # positions they attended to)})
        self.steps: List[Tuple[float, Dict[str, Tuple[int, int]]]] = []

    def add(self, ticket, prompt_tokens: int) -> None:
        with self.lock:
            self.prompt[id(ticket)] = prompt_tokens
            self.new.append(ticket)

    def __call__(self, step, telemetry, completed, now) -> None:
        try:
            self._stamp()
        except Exception:   # noqa: BLE001 — counted; a run with any is not correct
            self.errors += 1
            raise

    def _stamp(self) -> None:
        t = self.clock()
        with self.lock:
            self.live.extend(self.new)
            self.new = []
        still = []
        active: Dict[str, List[int]] = {}
        for tk in self.live:
            req = tk.request
            if req is None:
                if not tk.done:
                    still.append(tk)
                continue
            key = id(tk)
            n = len(req.output_tokens)
            had = self.seen.get(key, 0)
            if n > had:
                if had == 0:
                    self.first[key] = t
                self.stamps.setdefault(key, []).extend([t] * (n - had))
                self.seen[key] = n
                a = active.setdefault(req.backend, [0, 0])
                a[0] += 1
                a[1] += self.prompt[key] + n
            if not tk.done:
                still.append(tk)
        self.live = still
        self.steps.append((t, {b: (a[0], a[1]) for b, a in active.items()}))
