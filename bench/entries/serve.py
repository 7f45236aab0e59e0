"""Serve cells: requests through ``AsyncIngress.submit`` over a
``RouterService`` built as ``repro.launch.serve`` builds it (slot
scheduler, serve defaults, the configuration's prefill chunk), with the
router in front of the backends.

Open loop: one client thread submits each request at its due time.  An
``on_step`` hook on the serving thread stamps each request's tokens as
they appear (no streaming exists, so no client could see one earlier).
All times are on the batcher's clock, the one ``Request.arrival_s`` is
stamped on.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from bench import observe


def _pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())


def prepare(run) -> None:
    from repro.serving.router import RouterService
    b = run.built
    svc = RouterService(b.dsl, embedder=run.embedder(), **b.service)
    run.svc = svc
    run.recorder = observe.RouteRecorder(svc, run.spans)
    if run.spans:
        observe.wrap(svc, "serve_step", "bench.serve_step")
    _warm(run, svc)


def _by_backend(run, svc, nbytes: int, count: int, rng, tag: str):
    """At least ``count`` set-up prompts of ``nbytes`` for each backend."""
    out: Dict[str, List[str]] = {}
    tries = 0
    while min((len(out.get(n, [])) for n in svc.backends),
              default=0) < count and tries < 50:
        texts = run.built.make_texts(run.warm_items(32, nbytes=nbytes), rng,
                                     f"{tag}{tries}-")
        for t, a in zip(texts, svc.route_actions(texts)):
            out.setdefault(a.partition(":")[2], []).append(t)
        tries += 1
    return out


def _warm(run, svc) -> None:
    """Route every batch bucket the ingress can send, then admit on each
    backend every prompt shape the run's requests take and run the
    pooled decode step: a prompt longer than the prefill chunk goes
    through the chunked program (one shape), a shorter one through the
    single-shot prefill's (batch, prompt) buckets."""
    b = run.built
    rng = np.random.default_rng(run.seed ^ 0x5EED)
    k = 1
    while k <= 16:
        svc.route(b.make_texts(run.warm_items(k), rng, f"wr{k}-"))
        k *= 2
    half = run.cfg["max_seq"] // 2
    chunk = b.service.get("prefill_chunk") or 0
    lens = {min(it.prompt_bytes, half) for it in run.items}
    slots = b.service["slots"]
    sizes = sorted({_pow2(k) for k in range(1, slots + 1)})
    counts = [max(k for k in range(1, slots + 1) if _pow2(k) == s)
              for s in sizes]
    jobs = [(p, counts) for p in sorted(
        {_pow2(n) for n in lens if not chunk or n <= chunk})]
    if chunk and max(lens) > chunk:
        jobs.append((max(lens), [1]))
    for nbytes, ks in jobs:
        texts = _by_backend(run, svc, nbytes, max(ks), rng, f"wp{nbytes}-")
        for k in ks:
            for name in svc.backends:
                svc.enqueue(texts[name][:k], max_new_tokens=2)
                texts[name] = texts[name][k:]
            while svc._has_pending_work():
                svc.serve_step()


def window(run, tracer) -> None:
    from repro.serving.ingress import AsyncIngress
    svc = run.svc
    clock = svc.cbatcher.clock
    hook = observe.TokenClock(clock)
    run.hook = hook
    front = AsyncIngress(svc, on_step=hook)
    run.front = front
    items, texts = run.items, run.built.texts
    tickets: List[Any] = [None] * len(items)
    due = np.full(len(items), np.nan)
    sent = np.full(len(items), np.nan)
    half = run.cfg["max_seq"] // 2
    front.start()
    start = clock()
    end = start + run.seconds
    tracer.poll(0.0)
    for i, it in enumerate(items):
        t_due = start + it.due_s
        while True:
            now = clock()
            tracer.poll(now - start)
            if now >= t_due:
                break
            time.sleep(min(t_due - now, 0.002))
        tk = front.submit(texts[i], max_new_tokens=it.output_tokens)
        sent[i] = clock()
        due[i] = t_due
        hook.add(tk, min(len(texts[i].encode()), half))
        tickets[i] = tk
    while clock() < end:
        tracer.poll(clock() - start)
        time.sleep(0.002)
    run.window_s = clock() - start
    tracer.finish(run.window_s)
    run.window_span = (start, end)
    if tracer.t_on is not None:
        run.trace_span = (start + tracer.t_on, start + tracer.t_off)
    # every request due in the window gets its first token, however
    # late (its wait is its latency)
    limit = clock() + run.tail_s
    while clock() < limit and any(
            t is not None and not t.done and id(t) not in hook.first
            for t in tickets):
        time.sleep(0.01)
    summary = front.drain(timeout_s=run.drain_s)
    run.stats.update(_stats(tickets, due, sent, start, end, hook, summary))
    run.tickets = tickets


def _stats(tickets, due, sent, start, end, hook, summary):
    """Latencies and counts of the window.  A request fails when it ends
    failed, rejected, shed or timed out, or never gets its first token."""
    ttft, gaps, waits, late = [], [], [], []
    tokens_in = failed = attempted = 0
    for i, tk in enumerate(tickets):
        if tk is None:
            continue
        attempted += 1
        key = id(tk)
        if key in hook.first:
            ttft.append((hook.first[key] - due[i]) * 1e3)
        if key not in hook.first or tk.status in (
                "failed", "rejected", "shed", "timed_out"):
            failed += 1
        st = hook.stamps.get(key, [])
        gaps.extend((b - a) * 1e3 for a, b in zip(st, st[1:]) if b <= end)
        tokens_in += sum(1 for s in st if start <= s <= end)
        req = tk.request
        if req is not None and req.arrival_s is not None:
            # (due time, due time to the start of the serving turn that
            # took the request in)
            waits.append((due[i], (req.arrival_s - due[i]) * 1e3))
        late.append((sent[i] - due[i]) * 1e3)
    return {"ttft_ms": np.array(ttft), "gap_ms": np.array(gaps),
            "admission": waits,
            "lateness_ms": np.array(late), "tokens_in_window": tokens_in,
            "attempted": attempted, "failed": failed,
            "hook_errors": hook.errors,
            "crashed_steps": summary["crashed_steps"]}


def _samples(run) -> Dict[str, List[Dict[str, Any]]]:
    """A seeded sample of finished requests per backend, with each
    backend's longest, until ``decode_tokens`` served tokens are in."""
    rng = np.random.default_rng(run.seed ^ 0xDEC0DE)
    by_backend: Dict[str, List[Any]] = {}
    for tk in run.tickets:
        if tk is None or tk.status != "done":
            continue
        req = tk.request
        if req.truncated or req.fallback_used or not req.output_tokens:
            continue
        by_backend.setdefault(req.backend, []).append(req)
    out: Dict[str, List[Dict[str, Any]]] = {}
    max_seq = run.cfg["max_seq"]
    for name, reqs in sorted(by_backend.items()):
        vocab = run.backend(name)["shapes"]["vocab_size"]
        longest = max(range(len(reqs)),
                      key=lambda i: len(reqs[i].output_tokens))
        order = [longest] + [int(i) for i in rng.permutation(len(reqs))
                             if i != longest]
        picked, total = [], 0
        for i in order:
            if total >= run.decode_tokens:
                break
            req = reqs[i]
            # the prompt as served: its bytes, cut where the router cuts
            # them, at positions from 0 with nothing before it
            toks = [c % vocab for c in req.text.encode()[: max_seq // 2]]
            out_t = list(req.output_tokens)
            picked.append({"tokens": toks + out_t[:-1],
                           "first": len(toks) - 1, "served": out_t})
            total += len(out_t)
        out[name] = picked
    return out


def verify(run) -> Dict[str, Any]:
    """Routing and served-token numbers of the window's requests; with
    ``run.control`` the lower-precision control stands in the program's
    place and its numbers go through the same limits."""
    from bench.reference import decoder
    from bench.reference.router import compare, decide
    b, rec = run.built, run.recorder
    texts = [t.text for t in run.tickets if t is not None
             and t.text in rec.by_text]
    rng = np.random.default_rng(run.seed ^ 0xC0FFEE)
    if len(texts) > run.sample:
        texts = [texts[i] for i in sorted(
            rng.choice(len(texts), size=run.sample, replace=False))]
    got = [(run.svc.tables.rule_name(rec.by_text[t][0]), rec.by_text[t][1])
           for t in texts]
    samples = _samples(run)
    run.release()
    policy, embed = b.reference()
    emb = embed(texts)
    ref = decide(policy, emb)
    if run.control:
        from bench.entries.route import control_numbers
        out = control_numbers(policy, emb, ref, b.tie_margin)
        gaps = [_control_gaps(run.backend(name), smp)
                for name, smp in samples.items()]
    else:
        out = compare([g[0] for g in got], np.array([g[1] for g in got]),
                      ref, b.tie_margin)
        gaps = [decoder.served_gaps(run.backend(name)["shapes"],
                                    run.backend(name)["seed"], smp)["gap"]
                for name, smp in samples.items()]
    out.update(_gap_numbers(gaps))
    out["served_tokens_checked"] = sum(len(g) for g in gaps)
    return out


def _gap_numbers(gaps) -> Dict[str, float]:
    """Widest and mean gap of the served tokens below the reference's
    best logit; a run with nothing served to compare has shown nothing
    correct."""
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    if not g.size:
        return {"logit_gap": 1e30, "logit_gap_mean": 1e30}
    return {"logit_gap": float(g.max()), "logit_gap_mean": float(g.mean())}


def _control_gaps(be, smp) -> np.ndarray:
    """At each served position, the reference's gap of the token the
    fp8 control puts first."""
    from bench.reference import decoder
    top = decoder.served_gaps(be["shapes"], be["seed"], smp,
                              quant="fp8")["top"]
    alt, k = [], 0
    for s in smp:
        n = len(s["served"])
        alt.append(dict(s, served=[int(t) for t in top[k:k + n]]))
        k += n
    return decoder.served_gaps(be["shapes"], be["seed"], alt)["gap"]
