"""Sharded policy argmax: the routing decision without replicating
fired/conf across the mesh.

The PR 3 sharded serving path materialized the full (B, N) fired and
confidence matrices on every device (the shard_map signal layer's
outputs were scattered back to the replicated signal column space) and
then ran the replicated ``evaluate_policy`` on top.  This module keeps
the signal layer's outputs *sharded*: each device holds only its (Bl,
Nl) column shard of fired/conf, computes partial DNF-term sums over its
local atoms, and the partials meet in a single
``lax.psum_scatter(scatter_dimension=1, tiled=True)`` that hands each
device the fully-summed counts for its own chunk of terms — no device
ever sees the full fired matrix or the full term matrix.

Exactness.  ``got``/``blocked`` are sums of 0/1 indicators in f32, so
every partial is integer-valued and the psum is order-independent and
bitwise-equal to the replicated count.  Term confidence is a max (not a
sum), so it rides ``all_to_all`` + a local max over source devices —
also order-independent.  The winner is then the staged lexicographic
argmax evaluated in *term space*: every term of a rule carries the
rule's tier/priority, so restricting (tier, then priority, then clipped
confidence) to satisfied terms selects exactly the rules
``evaluate_policy``'s rule-space reduction would, and the final
``pmin`` over global rule indices attaining the best reproduces
``jnp.argmax``'s first-occurrence (lowest-index) tie-break.  Only (B,)
vectors cross devices after the scatter.

Term layout.  ``build_policy_shard_tables`` pads and partitions the DNF
term table into ``n_model`` equal chunks aligned to *rule boundaries* —
a rule's terms never split across devices, so the OR-over-terms and
max-over-terms rule aggregations stay device-local (they are implicit
in the term-space reduction).  Dead padding terms carry an unmeetable
``need`` so they can never satisfy.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

BIG_NEED = np.float32(1e30)     # dead padding terms can never satisfy
BIG_RULE = np.int32(2 ** 30)    # pmin identity for the rule-index race


def build_policy_shard_tables(tables, *, prob_cols, crisp_cols,
                              n_model: int) -> Dict[str, np.ndarray]:
    """Lower ``PolicyTables``' literal-index lists to the rule-aligned
    sharded term layout.

    prob_cols/crisp_cols: the engine's signal-column indices (policy
    atom axis order is ``sorted(cfg.signals)`` — the same order the
    engine binds, so the columns select directly).  The probabilistic
    atom axis pads up to the model-axis multiple to match the sharded
    signal bundle's dead columns.

    -> numpy dict: ``pos_prob``/``neg_prob`` (Tp, Npad) sharded on the
    atom axis, ``pos_crisp``/``neg_crisp`` (Tp, Ac) and the per-term
    vectors ``need``/``tier_t``/``pri_t``/``rule_t`` (Tp,) sharded on
    the term axis; Tp = n_model * Tc with chunk k holding device k's
    whole-rule term slice.
    """
    prob_cols = np.asarray(prob_cols, np.int64)
    crisp_cols = np.asarray(crisp_cols, np.int64)
    n_prob = prob_cols.shape[0]
    npad = n_prob + (-n_prob) % max(n_model, 1)
    terms = [tables.terms(r) for r in range(tables.n_rules)]
    t_total = sum(len(t) for t in terms)

    # contiguous whole-rule partition, proportionally balanced: rule r
    # (terms [lo, hi)) lands in the chunk its term midpoint falls in
    chunks: list = [[] for _ in range(n_model)]
    lo = 0
    for r, rule_terms in enumerate(terms):
        hi = lo + len(rule_terms)
        if hi > lo:
            k = min(n_model - 1,
                    int((lo + hi - 1) // 2 * n_model / max(t_total, 1)))
            chunks[k].extend((r, p, n) for p, n in rule_terms)
        lo = hi
    tc = max(1, max(len(c) for c in chunks))

    tp = n_model * tc
    pos_prob = np.zeros((tp, npad), np.float32)
    neg_prob = np.zeros((tp, npad), np.float32)
    ac = crisp_cols.shape[0]
    pos_crisp = np.zeros((tp, ac), np.float32)
    neg_crisp = np.zeros((tp, ac), np.float32)
    need = np.full((tp,), BIG_NEED, np.float32)
    tier_t = np.zeros((tp,), np.float32)
    pri_t = np.zeros((tp,), np.float32)
    rule_t = np.full((tp,), BIG_RULE, np.int32)
    # policy atom -> its prob / crisp signal column (an atom bound to
    # neither still counts toward ``need``, as in evaluate_policy)
    prob_of = {int(a): j for j, a in enumerate(prob_cols)}
    crisp_of = {int(a): j for j, a in enumerate(crisp_cols)}
    for k, chunk in enumerate(chunks):
        for j, (ri, p, n) in enumerate(chunk):
            row = k * tc + j
            for atoms, prob_t, crisp_t in ((p, pos_prob, pos_crisp),
                                           (n, neg_prob, neg_crisp)):
                for a in atoms.tolist():
                    if a in prob_of:
                        prob_t[row, prob_of[a]] = 1.0
                    elif a in crisp_of:
                        crisp_t[row, crisp_of[a]] = 1.0
            need[row] = len(p)
            tier_t[row] = tables.tier[ri]
            pri_t[row] = tables.priority[ri]
            rule_t[row] = ri
    return {"pos_prob": pos_prob, "neg_prob": neg_prob,
            "pos_crisp": pos_crisp, "neg_crisp": neg_crisp,
            "need": need, "tier_t": tier_t, "pri_t": pri_t,
            "rule_t": rule_t}


def _policy_argmax_body(model_axis, n_rules: int, n_model: int):
    """Device-local half of the sharded argmax: local fired/conf shard
    in, (Bl,) route index + score out.  All cross-device traffic is the
    one psum_scatter / all_to_all over the term partials plus five (B,)
    pmax/pmin lines for the staged lexicographic reduction."""

    def tail(fired, conf, crisp_raw, thr_crisp, pt):
        f32 = jnp.float32
        f = fired.astype(f32)                                 # (Bl, Nl)
        gotp = f @ pt["pos_prob"].T                           # (Bl, Tp)
        blkp = f @ pt["neg_prob"].T
        pcp = jnp.max(jnp.where(pt["pos_prob"][None] > 0,
                                conf[:, None, :], 0.0), axis=-1)
        if model_axis:
            got = jax.lax.psum_scatter(gotp, model_axis,
                                       scatter_dimension=1, tiled=True)
            blk = jax.lax.psum_scatter(blkp, model_axis,
                                       scatter_dimension=1, tiled=True)
            pc = jax.lax.all_to_all(pcp, model_axis, split_axis=1,
                                    concat_axis=0, tiled=True)
            pc = pc.reshape(n_model, f.shape[0], -1).max(axis=0)
        else:
            got, blk, pc = gotp, blkp, pcp                    # (Bl, Tc)
        if pt["pos_crisp"].shape[1]:
            fc = (crisp_raw.astype(f32)
                  >= thr_crisp[None, :]).astype(f32)          # (Bl, Ac)
            cc = jnp.where(fc > 0, crisp_raw.astype(f32), 0.0)
            got = got + fc @ pt["pos_crisp"].T
            blk = blk + fc @ pt["neg_crisp"].T
            pc = jnp.maximum(pc, jnp.max(
                jnp.where(pt["pos_crisp"][None] > 0,
                          cc[:, None, :], 0.0), axis=-1))
        ok = (got >= pt["need"][None]) & (blk <= 0.0)         # (Bl, Tc)

        pmax = ((lambda v: jax.lax.pmax(v, model_axis)) if model_axis
                else (lambda v: v))
        pmin = ((lambda v: jax.lax.pmin(v, model_axis)) if model_axis
                else (lambda v: v))
        ninf = -jnp.inf
        t = jnp.where(ok, pt["tier_t"][None], ninf)
        gt = pmax(t.max(axis=-1))                             # (Bl,)
        m1 = ok & (t >= gt[:, None])
        pr = jnp.where(m1, pt["pri_t"][None], ninf)
        gp = pmax(pr.max(axis=-1))
        m2 = m1 & (pr >= gp[:, None])
        c = jnp.where(m2, jnp.clip(pc, 0.0, 1.0), ninf)
        gc = pmax(c.max(axis=-1))
        cand = jnp.where(m2 & (c >= gc[:, None]),
                         pt["rule_t"][None], BIG_RULE)
        gidx = pmin(cand.min(axis=-1))
        anyok = pmax(jnp.any(ok, axis=-1).astype(f32))
        route = jnp.where(anyok > 0, gidx, n_rules).astype(jnp.int32)
        score = jnp.where(anyok > 0, gc, ninf)
        return route, score

    return tail


_ST_KEYS = ("centroids", "qscale_row", "cls_row", "scale_row",
            "thr_row", "grp_row", "member_row", "default_row",
            "thr_crisp")
_PT_KEYS = ("pos_prob", "neg_prob", "pos_crisp", "neg_crisp",
            "need", "tier_t", "pri_t", "rule_t")


@functools.lru_cache(maxsize=32)
def sharded_route_policy(mesh: Mesh, n_rules: int,
                         body_kernel: str = "jnp",
                         interpret: bool = False):
    """Jitted end-to-end sharded routing decision: embeddings + crisp
    scores -> (route idx (B,), score (B,)), with the signal layer's
    fired/conf never leaving their device shards.  Expects the engine's
    sharded signal bundle (``_build_sharded_bundle``) and the
    ``build_policy_shard_tables`` bundle; B must already be padded to
    the mesh's data-axes multiple (the router's bucket logic does
    this).  Decision- and score-bitwise-equal to the replicated
    ``evaluate_policy`` over the sharded signal eval."""
    from repro.signals.engine import (_mesh_batch_axes,
                                      _sharded_route_body, auto_axes)
    mesh = auto_axes(mesh)
    daxes = _mesh_batch_axes(mesh)
    maxis = "model" if "model" in mesh.shape else None
    n_model = mesh.shape.get("model", 1)
    sig_body = _sharded_route_body(maxis, body_kernel, interpret)
    pol_tail = _policy_argmax_body(maxis, n_rules, n_model)

    def body(emb, crisp_raw, st, pt):
        with jax.named_scope("signal"):
            _, scores, fired, _, _ = sig_body(
                emb, st["centroids"], st["qscale_row"], st["cls_row"],
                st["scale_row"], st["thr_row"], st["grp_row"],
                st["member_row"], st["default_row"])
            conf = jnp.where(fired, scores, 0.0)
        with jax.named_scope("policy"):
            return pol_tail(fired, conf, crisp_raw, st["thr_crisp"], pt)

    bspec = P(daxes if daxes else None, None)
    rspec = P(None, maxis)
    vspec = P(daxes if daxes else None)
    st_specs = {"centroids": P(maxis, None), "qscale_row": rspec,
                "cls_row": rspec, "scale_row": rspec, "thr_row": rspec,
                "grp_row": rspec, "member_row": rspec,
                "default_row": rspec, "thr_crisp": P(None)}
    pt_specs = {"pos_prob": P(None, maxis), "neg_prob": P(None, maxis),
                "pos_crisp": P(maxis, None),
                "neg_crisp": P(maxis, None), "need": P(maxis),
                "tier_t": P(maxis), "pri_t": P(maxis),
                "rule_t": P(maxis)}
    sh = jax.shard_map(body, mesh=mesh,
                       in_specs=(bspec, bspec, st_specs, pt_specs),
                       out_specs=(vspec, vspec), check_vma=False)

    @jax.jit
    def fn(emb, crisp_raw, st, pt):
        return sh(emb.astype(jnp.float32), crisp_raw,
                  {k: st[k] for k in _ST_KEYS},
                  {k: pt[k] for k in _PT_KEYS})

    return fn
