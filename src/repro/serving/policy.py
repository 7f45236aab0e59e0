"""Tensorized policy evaluation.

The DSL compiler's rule list is lowered once to dense tables (DNF literal
masks, priority/tier vectors), so routing an entire request batch is one
jit'd evaluation — the TPU-idiomatic replacement for a per-request
first-match interpreter (DESIGN §3).  Semantics preserved exactly:

  winner = argmax over fired rules of (tier, priority, confidence)
  confidence = max normalized score over the matched rule's positive atoms
  fallback   = default action when nothing fires

TIER routing (paper §5, item 5): tiers dominate priority; within a tier,
priority dominates confidence; equal-priority ties break on confidence —
"priority-then-confidence".

The jitted evaluator is cached at module level and ``PolicyTables``
caches its device-resident view, so per-batch work is exactly one cached
XLA call — no retracing, no host->device table transfer.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.conditions import to_dnf_atoms
from repro.dsl.compiler import RouterConfig

BIG = 1024.0 * 1024.0


@dataclasses.dataclass
class PolicyTables:
    atom_names: List[str]
    rule_names: List[str]
    actions: List[str]            # per rule, + [default] at index n_rules
    pos: np.ndarray               # (T, A) term requires atom fired
    neg: np.ndarray               # (T, A) term requires atom NOT fired
    term_rule: np.ndarray         # (T,) owning rule index
    priority: np.ndarray          # (R,)
    tier: np.ndarray              # (R,)
    n_rules: int
    _jax: Optional[Dict[str, jnp.ndarray]] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    def as_jax(self) -> Dict[str, jnp.ndarray]:
        """Device-resident view, transferred once and cached — callers
        hit the same buffers on every batch."""
        if self._jax is None:
            self._jax = {k: jnp.asarray(getattr(self, k))
                         for k in ("pos", "neg", "term_rule", "priority",
                                   "tier")}
        return self._jax

    def action_key(self, i: int) -> str:
        return self.actions[int(i)]

    def rule_name(self, i: int) -> str:
        return (self.rule_names[int(i)] if int(i) < self.n_rules
                else "__default__")


def build_tables(cfg: RouterConfig) -> PolicyTables:
    atoms = sorted(cfg.signals)
    aidx = {a: i for i, a in enumerate(atoms)}
    pos_rows, neg_rows, term_rule = [], [], []
    rule_names, actions = [], []
    for ri, rule in enumerate(cfg.rules):
        rule_names.append(rule.name)
        actions.append(cfg.actions[rule.name].key())
        for (p, n) in to_dnf_atoms(rule.condition):
            pr = np.zeros(len(atoms), np.float32)
            nr = np.zeros(len(atoms), np.float32)
            for a in p:
                pr[aidx[a]] = 1.0
            for a in n:
                nr[aidx[a]] = 1.0
            pos_rows.append(pr)
            neg_rows.append(nr)
            term_rule.append(ri)
    default = cfg.default_action
    actions.append(default.key() if default else "model:__reject__")
    return PolicyTables(
        atom_names=atoms, rule_names=rule_names, actions=actions,
        pos=np.stack(pos_rows) if pos_rows else np.zeros((0, len(atoms)), np.float32),
        neg=np.stack(neg_rows) if neg_rows else np.zeros((0, len(atoms)), np.float32),
        term_rule=np.asarray(term_rule, np.int32),
        priority=np.asarray([r.priority for r in cfg.rules], np.float32),
        tier=np.asarray([r.tier for r in cfg.rules], np.float32),
        n_rules=len(cfg.rules))


@jax.named_scope("policy")
def evaluate_policy(tables: Dict[str, jnp.ndarray], n_rules: int,
                    fired: jnp.ndarray, confidence: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """fired/confidence: (B, A) -> (route_idx (B,), score (B,)).
    route_idx == n_rules means the default action."""
    f = fired.astype(jnp.float32)
    pos, neg = tables["pos"], tables["neg"]
    need = pos.sum(axis=1)                                   # (T,)
    got = f @ pos.T                                          # (B, T)
    blocked = f @ neg.T                                      # (B, T)
    term_ok = (got >= need[None]) & (blocked <= 0.0)
    # rule fires if any of its terms do
    rule_ok = jnp.zeros((f.shape[0], n_rules), bool)
    rule_ok = rule_ok.at[:, tables["term_rule"]].max(term_ok)
    # rule confidence: max positive-atom confidence over satisfied terms
    term_conf = jnp.where(
        term_ok,
        jnp.max(jnp.where(pos[None] > 0, confidence[:, None, :], 0.0),
                axis=-1),
        0.0)
    rule_conf = jnp.zeros((f.shape[0], n_rules), term_conf.dtype)
    rule_conf = rule_conf.at[:, tables["term_rule"]].max(term_conf)
    # exact staged lexicographic argmax over (tier, priority, confidence):
    # a single scalarized score (tier*B^2 + pri*B + conf) loses the
    # confidence tie-break to f32 rounding at high tiers (found by
    # hypothesis — see tests/test_policy_eval.py)
    ninf = -jnp.inf
    t = jnp.where(rule_ok, tables["tier"][None], ninf)
    m1 = rule_ok & (t >= t.max(axis=-1, keepdims=True))
    pr = jnp.where(m1, tables["priority"][None], ninf)
    m2 = m1 & (pr >= pr.max(axis=-1, keepdims=True))
    c = jnp.where(m2, jnp.clip(rule_conf, 0.0, 1.0), ninf)
    best = jnp.argmax(c, axis=-1)
    best_score = jnp.take_along_axis(c, best[:, None], axis=1)[:, 0]
    none = ~jnp.any(rule_ok, axis=-1)
    route = jnp.where(none, n_rules, best)
    return route, jnp.where(none, -jnp.inf, best_score)


# one persistent jit cache for every caller — rebuilding jax.jit(...) per
# batch (the old route_batch/route_names) retraced on every request
_EVAL_JIT = jax.jit(evaluate_policy, static_argnums=(1,))


def evaluate_indices(tables: PolicyTables, fired, confidence
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """(route index, score) per request via the cached jit + cached
    device tables.  index == n_rules means the default action."""
    idx, score = _EVAL_JIT(tables.as_jax(), tables.n_rules,
                           jnp.asarray(fired), jnp.asarray(confidence))
    return np.asarray(idx), np.asarray(score)


def route_batch(tables: PolicyTables, fired: np.ndarray,
                confidence: np.ndarray) -> List[str]:
    """Convenience numpy wrapper -> winning action key per request."""
    idx, _ = evaluate_indices(tables, fired, confidence)
    return [tables.action_key(i) for i in idx]


def route_names(tables: PolicyTables, fired, confidence) -> List[str]:
    idx, _ = evaluate_indices(tables, fired, confidence)
    return [tables.rule_name(i) for i in idx]
