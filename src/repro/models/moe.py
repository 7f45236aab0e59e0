"""Mixture-of-Experts FFN with two execution strategies.

``moe_impl="dense"``    — every expert runs on every token, combined by the
                          (sparse) gate.  Exact, simple, FLOP-wasteful: the
                          baseline the roofline "useful-FLOP ratio" exposes.
``moe_impl="dispatch"`` — Switch-style capacity dispatch: tokens are
                          scattered to (expert, slot) buffers via one-hot
                          einsums.  REFUTED as an optimization in
                          EXPERIMENTS.md §Perf H3-iter1: the one-hot
                          dispatch matmul is O(N·E·C·d) and dominates.
``moe_impl="sort"``     — sort-based gather dispatch under implicit SPMD.
                          REFUTED as a *distributed* optimization in
                          EXPERIMENTS.md §Perf H3-iter2: whole-array
                          scatter/gather defeat the partitioner (12×
                          collective blow-up).  Kept as the single-device
                          correctness/fallback path.
``moe_impl="ep"``       — explicit expert parallelism via shard_map over
                          the "model" axis: activations are replicated
                          across that axis, so each rank locally gathers
                          only the tokens routed to ITS experts
                          (capacity-limited), runs them, scatters back,
                          and psums.  Same collective volume as dense
                          (one psum/layer), ~E/(1.25·k) less routed-FFN
                          compute.  The confirmed §Perf optimization
                          (H3-iter3).

The router is itself a Thm-2 object: ``router_temperature`` scales the
logits; top-1 routing is exactly a Voronoi partition of hidden space
(paper §5/DESIGN §5), and expert co-activation stats are reported by
``benchmarks/bench_moe_voronoi.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common as cm


def init_moe(key, cfg: ModelConfig):
    dt = cm.dtype_of(cfg.dtype)
    d, m = cfg.d_model, cfg.moe
    ks = jax.random.split(key, 8)
    p = {
        "router": cm.dense_init(ks[0], (d, m.n_routed), jnp.float32),
        "e_gate": cm.dense_init(ks[1], (m.n_routed, d, m.d_ff_expert), dt, in_axis=1),
        "e_up": cm.dense_init(ks[2], (m.n_routed, d, m.d_ff_expert), dt, in_axis=1),
        "e_down": cm.dense_init(ks[3], (m.n_routed, m.d_ff_expert, d), dt, in_axis=1),
    }
    if m.n_shared:
        p["s_gate"] = cm.dense_init(ks[4], (d, m.d_ff_shared), dt)
        p["s_up"] = cm.dense_init(ks[5], (d, m.d_ff_shared), dt)
        p["s_down"] = cm.dense_init(ks[6], (m.d_ff_shared, d), dt)
    return p


def router_weights(p, cfg: ModelConfig, x):
    """-> (gates (B,S,E) sparse combine weights, logits f32, topk idx)."""
    m = cfg.moe
    logits = (x.astype(jnp.float32) @ p["router"]) / m.router_temperature
    if m.score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(scores, m.top_k)
    if m.norm_topk and m.top_k > 1:
        top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(top_idx, m.n_routed, dtype=scores.dtype)  # (B,S,K,E)
    gates = jnp.einsum("bske,bsk->bse", onehot, top_vals)
    return gates, logits, top_idx


def aux_load_balance_loss(logits, top_idx, n_experts: int):
    """Switch-style load-balance auxiliary loss."""
    probs = jax.nn.softmax(logits, axis=-1)
    frac_routed = jnp.mean(
        jax.nn.one_hot(top_idx[..., 0], n_experts, dtype=jnp.float32),
        axis=(0, 1))
    frac_prob = jnp.mean(probs, axis=(0, 1))
    return n_experts * jnp.sum(frac_routed * frac_prob)


def _expert_ffn(p, h):
    """h: (E, N, D) per-expert token buffers."""
    g = jax.nn.silu(jnp.einsum("end,edf->enf", h, p["e_gate"]))
    u = jnp.einsum("end,edf->enf", h, p["e_up"])
    return jnp.einsum("enf,efd->end", g * u, p["e_down"])


def apply_moe(p, cfg: ModelConfig, x):
    """-> (y, aux_loss).  x: (B, S, D)."""
    m = cfg.moe
    gates, logits, top_idx = router_weights(p, cfg, x)
    if cfg.moe_impl == "dense":
        # all experts on all tokens; combine with sparse gates
        g = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, p["e_gate"]))
        u = jnp.einsum("bsd,edf->bsef", x, p["e_up"])
        yo = jnp.einsum("bsef,efd->bsed", g * u, p["e_down"])
        y = jnp.einsum("bsed,bse->bsd", yo, gates.astype(yo.dtype))
    elif cfg.moe_impl == "sort":
        y = _sort_moe(p, cfg, x, top_idx,
                      jnp.take_along_axis(gates, top_idx, axis=-1))
    elif cfg.moe_impl == "ep":
        y = _ep_moe(p, cfg, x)
    else:
        y = _dispatch_moe(p, cfg, x, gates)
    if m.n_shared:
        y = y + (jax.nn.silu(x @ p["s_gate"]) * (x @ p["s_up"])) @ p["s_down"]
    aux = aux_load_balance_loss(logits, top_idx, m.n_routed)
    return y, aux


def _sort_moe(p, cfg: ModelConfig, x, top_idx, top_gates):
    """Sort-based gather dispatch (EXPERIMENTS.md §Perf H3-iter2).

    argsort the (token, k) assignments by expert id, gather the tokens
    into an (E, C) capacity-padded buffer via take (O(N·k·d) movement),
    run the experts on contiguous blocks, scatter-add back.  Capacity
    overflow drops the lowest-rank assignments (standard Switch drop)."""
    m = cfg.moe
    b, s, d = x.shape
    n = b * s
    xf = x.reshape(n, d)
    k = m.top_k
    flat_expert = top_idx.reshape(n * k)               # (N*k,)
    flat_tok = jnp.repeat(jnp.arange(n), k)
    flat_gate = top_gates.reshape(n * k)
    order = jnp.argsort(flat_expert, stable=True)
    se, stok, sgate = (flat_expert[order], flat_tok[order],
                       flat_gate[order])
    capacity = max(1, int(1.25 * n * k / m.n_routed))
    # position of each sorted assignment within its expert's block
    pos_in_e = jnp.arange(n * k) - jnp.searchsorted(se, se, side="left")
    keep = pos_in_e < capacity
    slot = se * capacity + jnp.where(keep, pos_in_e, 0)
    # gather tokens to buffers: (E*C, d)
    buf = jnp.zeros((m.n_routed * capacity, d), xf.dtype)
    src = jnp.where(keep, stok, n)                     # n -> dummy row
    xf_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)])
    buf = buf.at[slot].set(jnp.where(keep[:, None], xf_pad[src], 0.0))
    buffers = buf.reshape(m.n_routed, capacity, d)
    out = _expert_ffn(p, buffers).reshape(m.n_routed * capacity, d)
    contrib = out[slot] * (sgate * keep)[:, None].astype(out.dtype)
    y = jnp.zeros((n + 1, d), xf.dtype).at[src].add(contrib)[:n]
    return y.reshape(b, s, d)


def _local_capacity_ffn(p_local, cfg: ModelConfig, xf, top_idx, top_gates,
                        e_lo, e_local: int, capacity: int):
    """Capacity-limited FFN over the tokens routed to experts in
    [e_lo, e_lo + e_local) — indexing is rank-local, SPMD-safe.  e_local
    and capacity are static; e_lo may be a traced axis_index."""
    m = cfg.moe
    n, d = xf.shape
    k = m.top_k
    flat_e = top_idx.reshape(n * k)
    flat_tok = jnp.repeat(jnp.arange(n), k)
    flat_g = top_gates.reshape(n * k)
    mine = (flat_e >= e_lo) & (flat_e < e_lo + e_local)
    loc_e = jnp.where(mine, flat_e - e_lo, e_local)       # e_local = dummy
    order = jnp.argsort(loc_e, stable=True)
    se, stok, sg, sm = (loc_e[order], flat_tok[order], flat_g[order],
                        mine[order])
    pos = jnp.arange(n * k) - jnp.searchsorted(se, se, side="left")
    keep = sm & (pos < capacity)
    slot = jnp.where(keep, se * capacity + pos, e_local * capacity)
    buf = jnp.zeros((e_local * capacity + 1, d), xf.dtype)
    xf_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)])
    src = jnp.where(keep, stok, n)
    buf = buf.at[slot].set(jnp.where(keep[:, None], xf_pad[src], 0.0))
    buffers = buf[:-1].reshape(e_local, capacity, d)
    out = _expert_ffn(p_local, buffers).reshape(e_local * capacity, d)
    out = jnp.concatenate([out, jnp.zeros((1, d), out.dtype)])
    contrib = out[slot] * (sg * keep)[:, None].astype(out.dtype)
    y = jnp.zeros((n + 1, d), xf.dtype).at[src].add(contrib)[:n]
    return y


def _ep_moe(p, cfg: ModelConfig, x):
    """Expert parallelism via shard_map over the 'model' mesh axis
    (EXPERIMENTS.md §Perf H3-iter3).  Falls back to the local sort path
    when no mesh is active or experts don't divide the axis."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed import sharding as shd

    m = cfg.moe
    mesh = shd.current_mesh()
    n_model = mesh.shape.get("model", 1) if mesh is not None else 1
    if mesh is None or n_model == 1 or m.n_routed % n_model != 0:
        gates, _, top_idx = router_weights(p, cfg, x)
        return _sort_moe(p, cfg, x, top_idx,
                         jnp.take_along_axis(gates, top_idx, axis=-1))

    b, s, d = x.shape
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    bspec = batch_axes if (batch_axes and b % _prod_axes(mesh, batch_axes) == 0) \
        else None
    x_spec = P(bspec, None, None)
    e_local = m.n_routed // n_model

    def body(xb, router, eg, eu, ed):
        nb, sb, _ = xb.shape
        xf = xb.reshape(nb * sb, d)
        logits = (xf.astype(jnp.float32) @ router) / m.router_temperature
        scores = jax.nn.sigmoid(logits) if m.score_func == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        top_vals, top_idx = jax.lax.top_k(scores, m.top_k)
        if m.norm_topk and m.top_k > 1:
            top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
        r = jax.lax.axis_index("model")
        capacity = max(1, int(1.25 * nb * sb * m.top_k / m.n_routed))
        y = _local_capacity_ffn(
            {"e_gate": eg, "e_up": eu, "e_down": ed}, cfg, xf,
            top_idx, top_vals.astype(xf.dtype),
            r * e_local, e_local, capacity)
        y = jax.lax.psum(y, "model")
        return y.reshape(nb, sb, d)

    y = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=x_spec, check_vma=False,
    )(x, p["router"], p["e_gate"], p["e_up"], p["e_down"])
    return y


def _prod_axes(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _dispatch_moe(p, cfg: ModelConfig, x, gates):
    """Capacity-based dispatch (capacity_factor 1.25 over the top-k mean)."""
    m = cfg.moe
    b, s, d = x.shape
    n = b * s
    xf = x.reshape(n, d)
    gf = gates.reshape(n, m.n_routed)
    capacity = max(1, int(1.25 * n * m.top_k / m.n_routed))
    fires = gf > 0                                        # (N, E)
    # position of each token within its expert's buffer
    rank = jnp.cumsum(fires.astype(jnp.int32), axis=0) - 1  # (N, E)
    keep = fires & (rank < capacity)
    disp = (jax.nn.one_hot(rank, capacity, dtype=xf.dtype)
            * keep[..., None].astype(xf.dtype))          # (N, E, C)
    buffers = jnp.einsum("nec,nd->ecd", disp, xf)        # (E, C, D)
    out = _expert_ffn(p, buffers)                        # (E, C, D)
    combine = disp * gf[..., None].astype(xf.dtype)      # (N, E, C)
    y = jnp.einsum("nec,ecd->nd", combine, out)
    return y.reshape(b, s, d)
