"""Tensorized policy evaluation.

The DSL compiler's rule list is lowered once to literal-index tables
(each rule's DNF terms, each term's atom indices, priority/tier
vectors), so routing an entire request batch is one jit'd evaluation —
the TPU-idiomatic replacement for a per-request first-match interpreter
(DESIGN §3).  Semantics preserved exactly:

  winner = argmax over fired rules of (tier, priority, confidence)
  confidence = max normalized score over the matched rule's positive atoms
  fallback   = default action when nothing fires

TIER routing (paper §5, item 5): tiers dominate priority; within a tier,
priority dominates confidence; equal-priority ties break on confidence —
"priority-then-confidence".

Layout.  Terms are grouped by rule and padded to rectangles whose
widths come from the compiled policy: M (most terms of any rule), Kp
and Kn (most positive / negative literals of any term).  A padded
literal slot points at atom column A, appended at evaluation time as
"never fired, confidence 0"; a padded term needs ``PAD_NEED`` positive
literals, so it never holds.  The tables grow with the literals, not
with terms x atoms: at 16k one-atom routes a call gathers 16k rows of
the (A+1, 2B) operand where a (term, atom) mask would stream 1 GB.

The jitted evaluator is cached at module level and ``PolicyTables``
caches its device-resident view, so per-batch work is exactly one cached
XLA call — no retracing, no host->device table transfer.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.conditions import to_dnf_atoms
from repro.dsl.compiler import RouterConfig

PAD_NEED = np.int32(2 ** 30)    # a padded term can never be met


@dataclasses.dataclass
class PolicyTables:
    atom_names: List[str]
    rule_names: List[str]
    actions: List[str]            # per rule, + [default] at index n_rules
    pos_idx: np.ndarray           # (R, M, Kp) positive literals' atoms
    neg_idx: np.ndarray           # (R, M, Kn) negative literals' atoms
    need: np.ndarray              # (R, M) positive literals per term
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
                         for k in ("pos_idx", "neg_idx", "need",
                                   "priority", "tier")}
        return self._jax

    def terms(self, r: int) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Rule ``r``'s DNF terms in order, each as (positive atom
        indices, negative atom indices) without padding."""
        pad = len(self.atom_names)
        return [(p[p != pad], n[n != pad]) for p, n, k in
                zip(self.pos_idx[r], self.neg_idx[r], self.need[r])
                if k != PAD_NEED]

    def stats(self) -> Dict[str, Any]:
        """Shape of the compiled DNF: ``rules``, real ``terms`` and
        ``literals``, the padding widths ``M``/``Kp``/``Kn``, and
        ``pad_share``, the share of gathered literal slots that are
        padding."""
        pad = len(self.atom_names)
        literals = int((self.pos_idx != pad).sum()
                       + (self.neg_idx != pad).sum())
        slots = self.pos_idx.size + self.neg_idx.size
        _, m, kp = self.pos_idx.shape
        return {"rules": self.n_rules,
                "terms": int((self.need != PAD_NEED).sum()),
                "literals": literals, "M": m, "Kp": kp,
                "Kn": self.neg_idx.shape[2],
                "pad_share": 1.0 - literals / slots if slots else 0.0}

    def action_key(self, i: int) -> str:
        return self.actions[int(i)]

    def rule_name(self, i: int) -> str:
        return (self.rule_names[int(i)] if int(i) < self.n_rules
                else "__default__")


def build_tables(cfg: RouterConfig) -> PolicyTables:
    atoms = sorted(cfg.signals)
    aidx = {a: i for i, a in enumerate(atoms)}
    rule_terms, rule_names, actions = [], [], []
    for rule in cfg.rules:
        rule_names.append(rule.name)
        actions.append(cfg.actions[rule.name].key())
        rule_terms.append([(sorted(aidx[a] for a in p),
                            sorted(aidx[a] for a in n))
                           for p, n in to_dnf_atoms(rule.condition)])
    default = cfg.default_action
    actions.append(default.key() if default else "model:__reject__")
    # widths from the policy itself; M and Kp keep one slot so the
    # rule and confidence maxima always have an operand
    m = max([1] + [len(t) for t in rule_terms])
    kp = max([1] + [len(p) for t in rule_terms for p, _ in t])
    kn = max([0] + [len(n) for t in rule_terms for _, n in t])
    pos_idx = np.full((len(rule_terms), m, kp), len(atoms), np.int32)
    neg_idx = np.full((len(rule_terms), m, kn), len(atoms), np.int32)
    need = np.full((len(rule_terms), m), PAD_NEED, np.int32)
    for r, terms in enumerate(rule_terms):
        for j, (p, n) in enumerate(terms):
            pos_idx[r, j, :len(p)] = p
            neg_idx[r, j, :len(n)] = n
            need[r, j] = len(p)
    return PolicyTables(
        atom_names=atoms, rule_names=rule_names, actions=actions,
        pos_idx=pos_idx, neg_idx=neg_idx, need=need,
        priority=np.asarray([r.priority for r in cfg.rules], np.float32),
        tier=np.asarray([r.tier for r in cfg.rules], np.float32),
        n_rules=len(cfg.rules))


@jax.named_scope("policy")
def evaluate_policy(tables: Dict[str, jnp.ndarray], n_rules: int,
                    fired: jnp.ndarray, confidence: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """fired/confidence: (B, A) -> (route_idx (B,), score (B,)).
    route_idx == n_rules means the default action."""
    pos_idx, neg_idx = tables["pos_idx"], tables["neg_idx"]
    r, m, kp = pos_idx.shape
    kn = neg_idx.shape[2]
    b = fired.shape[0]
    # atom-major operand, rows = atoms + the pad atom A, columns = fired
    # then confidence of every request: one row gather (the embedding-
    # lookup shape) serves both the literal count and the confidence max
    f = fired.astype(jnp.float32)
    lit = jnp.concatenate([f, confidence.astype(jnp.float32)], axis=0).T
    lit = jnp.pad(lit, ((0, 1), (0, 0)))                     # (A+1, 2B)
    g = jnp.take(lit, pos_idx.reshape(-1), axis=0, mode="clip"
                 ).reshape(r, m, kp, 2 * b)
    got = g[..., :b].sum(axis=2)                             # (R, M, B)
    term_ok = got >= tables["need"].astype(jnp.float32)[..., None]
    if kn:
        blocked = jnp.take(lit[:, :b], neg_idx.reshape(-1), axis=0,
                           mode="clip").reshape(r, m, kn, b).sum(axis=2)
        term_ok = term_ok & (blocked <= 0.0)
    # rule fires if any of its terms do; its confidence is the max
    # positive-literal confidence over satisfied terms (0 when none,
    # as is a padded slot's)
    term_conf = jnp.where(term_ok, g[..., b:].max(axis=2), 0.0)
    rule_ok = jnp.any(term_ok, axis=1).T                     # (B, R)
    rule_conf = jnp.max(term_conf, axis=1).T.astype(confidence.dtype)
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
