"""Compiler: DSL Program -> RouterConfig.

RouterConfig is the single runtime artifact: signal atoms (with group
membership), Voronoi groups, prioritized rules + actions, backends,
plugins, TEST suites, and validated DECISION_TREEs.  The serving layer
additionally lowers it to literal-index policy tables (serving/policy.py)
so a whole request batch routes with one jit'd evaluation.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

from repro.core import fdd
from repro.core.atoms import SignalAtom
from repro.core.taxonomy import Rule
from repro.core.voronoi import VoronoiGroup
from repro.dsl import ast


class CompileError(ValueError):
    pass


@dataclasses.dataclass(frozen=True)
class Action:
    kind: str                     # "model" | "plugin"
    target: str
    params: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def key(self) -> str:
        return f"{self.kind}:{self.target}"


@dataclasses.dataclass
class RouterConfig:
    signals: Dict[str, SignalAtom]
    signal_fields: Dict[str, Dict[str, Any]]
    groups: Dict[str, VoronoiGroup]
    rules: List[Rule]
    actions: Dict[str, Action]               # rule name -> action
    backends: Dict[str, Dict[str, Any]]
    plugins: Dict[str, Dict[str, Any]]
    global_fields: Dict[str, Any]
    tests: Dict[str, Tuple[Tuple[str, str], ...]]
    trees: Dict[str, fdd.DecisionTree]
    atom_types: Dict[str, str]
    source: str = ""                         # DSL text this was compiled from

    @property
    def default_action(self) -> Optional[Action]:
        m = self.global_fields.get("default_model")
        return Action("model", m) if m else None

    def exclusive_groups(self) -> List[Tuple[str, ...]]:
        return [g.names for g in self.groups.values()]

    def fingerprint(self) -> str:
        """Short content digest of the compiled source — the hot-swap
        no-op check (rebinding the identical policy is skipped)."""
        import hashlib
        return hashlib.sha1(self.source.encode("utf-8")).hexdigest()[:12]


DEFAULT_THRESHOLD = 0.5


def compile_program(prog: ast.Program,
                    atom_types: Optional[Dict[str, str]] = None
                    ) -> RouterConfig:
    atom_types = dict(atom_types or {})
    global_fields = dict(prog.global_.fields) if prog.global_ else {}
    default_thr = float(global_fields.get("threshold", DEFAULT_THRESHOLD))

    # ---- groups first (membership feeds the atoms) -------------------------
    groups: Dict[str, VoronoiGroup] = {}
    member_group: Dict[str, str] = {}
    for g in prog.groups:
        members = tuple(str(m) for m in g.fields.get("members", []))
        semantics = g.fields.get("semantics", "softmax_exclusive")
        if semantics not in ("softmax_exclusive", "independent"):
            raise CompileError(
                f"SIGNAL_GROUP {g.name}: unknown semantics {semantics!r}")
        temp = float(g.fields.get("temperature", 0.1))
        thr = float(g.fields.get("threshold", default_thr))
        default = g.fields.get("default")
        if semantics == "softmax_exclusive":
            groups[g.name] = VoronoiGroup(members, temp, thr,
                                          str(default) if default else None)
        for m in members:
            member_group[m] = g.name

    # ---- signals ------------------------------------------------------------
    signals: Dict[str, SignalAtom] = {}
    signal_fields: Dict[str, Dict[str, Any]] = {}
    for s in prog.signals:
        if s.name in signals:
            raise CompileError(f"duplicate SIGNAL {s.name!r}")
        cats = tuple(str(c) for c in s.fields.get("mmlu_categories", []))
        thr = float(s.fields.get("threshold", default_thr))
        signals[s.name] = SignalAtom(
            name=s.name, signal_type=s.signal_type, threshold=thr,
            categories=cats, group=member_group.get(s.name))
        signal_fields[s.name] = dict(s.fields)
        atom_types.setdefault(s.name, s.signal_type)

    # ---- routes -> rules + actions ------------------------------------------
    rules: List[Rule] = []
    actions: Dict[str, Action] = {}
    seen = set()
    for r in prog.routes:
        if r.name in seen:
            raise CompileError(f"duplicate ROUTE {r.name!r}")
        seen.add(r.name)
        if r.model is not None:
            action = Action("model", r.model)
        else:
            pname, pfields = r.plugin
            action = Action("plugin", pname, dict(pfields))
        rules.append(Rule(r.name, r.when, action.key(), r.priority, r.tier))
        actions[r.name] = action

    # ---- trees ---------------------------------------------------------------
    trees: Dict[str, fdd.DecisionTree] = {}
    for t in prog.trees:
        branches = []
        for i, b in enumerate(t.branches):
            if b.model is not None:
                act = Action("model", b.model)
            else:
                act = Action("plugin", b.plugin[0], dict(b.plugin[1]))
            branches.append(fdd.Branch(b.guard, act.key(),
                                       f"{t.name}_b{i}"))
        trees[t.name] = fdd.DecisionTree(t.name, tuple(branches))

    return RouterConfig(
        signals=signals,
        signal_fields=signal_fields,
        groups=groups,
        rules=rules,
        actions=actions,
        backends={b.name: dict(b.fields) for b in prog.backends},
        plugins={p.name: dict(p.fields) for p in prog.plugins},
        global_fields=global_fields,
        tests={t.name: t.cases for t in prog.tests},
        trees=trees,
        atom_types=atom_types,
    )


def compile_text(text: str) -> RouterConfig:
    from repro.dsl.parser import parse
    prog, atom_types = parse(text)
    cfg = compile_program(prog, atom_types)
    cfg.source = text
    return cfg
