"""Route-only cells: ``RouterService.route`` driven from one loop.

On each turn the loop routes every request due by then, at most
``admit_batch`` of them, as one call, as the ingress groups admissions.
A request's latency runs from its due time to the return of the call
that routed it, so a slow call delays the requests queued behind it.

By default the loop routes every request of the schedule, so a tail is
the tail of all of them.  A mix with ``"close": "stop"`` (traffic above
what the router sustains) sends nothing more once ``--seconds`` are up:
the window then ends when the last call returns, and the requests
still queued are not counted.
"""
from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

from bench import observe


def prepare(run) -> None:
    from repro.serving.router import RouterService
    b = run.built
    svc = RouterService(b.dsl, embedder=run.embedder(), load_backends=False,
                        **b.service)
    run.svc = svc
    run.recorder = observe.RouteRecorder(svc, run.spans)
    run.layout = observe.route_layout(svc)
    # every batch bucket the loop can send: 1, 2, 4, ..., admit_batch
    warm = b.make_texts(run.warm_items(run.mix["admit_batch"]),
                        np.random.default_rng(run.seed ^ 0x5EED), "w")
    k = 1
    while k <= run.mix["admit_batch"]:
        svc.route(warm[:k])
        k *= 2


def window(run, tracer) -> None:
    svc, rec = run.svc, run.recorder
    items, texts = run.items, run.built.texts
    cap = run.mix["admit_batch"]
    clock = time.perf_counter
    lat = np.full(len(items), np.nan)
    late = np.full(len(items), np.nan)
    due = np.array([it.due_s for it in items])
    call_ms, call_at, call_rows = [], [], []
    stop = run.mix.get("close") == "stop"
    nxt = 0
    start = clock()
    tracer.poll(0.0)
    while nxt < len(items):
        now = clock() - start
        if stop and now >= run.seconds:
            break
        tracer.poll(now)
        if due[nxt] > now:
            time.sleep(min(due[nxt] - now, 0.002))
            continue
        end = nxt
        while end < len(items) and end - nxt < cap and due[end] <= now:
            end += 1
        batch = list(range(nxt, end))
        sent = clock() - start
        rec.route([texts[i] for i in batch], batch)
        done = clock() - start
        lat[batch] = (done - due[batch]) * 1e3
        late[batch] = (sent - due[batch]) * 1e3
        call_ms.append((done - sent) * 1e3)
        call_at.append(sent)
        call_rows.append(len(batch))
        nxt = end
    run.window_s = clock() - start
    tracer.finish(run.window_s)
    run.stats.update(route_ms=lat[:nxt], lateness_ms=late[:nxt],
                     calls=len(call_ms), call_ms=np.array(call_ms),
                     call_at_s=np.array(call_at),
                     call_rows=np.array(call_rows),
                     attempted=nxt, failed=0, batch_rows=nxt)


def verify(run) -> Dict[str, Any]:
    """Routing numbers on a seeded sample of the window's requests (of
    the lower-precision control in the program's place, with
    ``run.control``)."""
    from bench.reference.router import compare, decide
    rec, b = run.recorder, run.built
    rows = sorted(rec.decisions)
    rng = np.random.default_rng(run.seed ^ 0xC0FFEE)
    take = rows if len(rows) <= run.sample else sorted(
        rng.choice(rows, size=run.sample, replace=False).tolist())
    texts = [b.texts[i] for i in take]
    got = [rec.decisions[i] for i in take]
    run.release()
    policy, embed = b.reference()
    emb = embed(texts)
    ref = decide(policy, emb)
    if run.control:
        return control_numbers(policy, emb, ref, b.tie_margin)
    return compare([g[0] for g in got], np.array([g[1] for g in got]), ref,
                   b.tie_margin)


def control_numbers(policy, emb, ref, tie_margin) -> Dict[str, Any]:
    """The reference at ``high`` (three bf16 passes) in the program's
    place, read by the same comparison."""
    from bench.reference.router import compare, decide
    low = decide(policy, emb, precision="high")
    return compare(low.route, low.score, ref, tie_margin)
