"""Tensorized policy evaluation ≡ the first-match interpreter, property-
tested over random rule sets and activations (hypothesis)."""
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.conditions import And, Atom, Not, Or, to_dnf_atoms
from repro.dsl.compiler import compile_text
from repro.serving import policy

ATOMS = ["s0", "s1", "s2", "s3"]


def interpreter(cfg, fired_row, conf_row, atom_names):
    """Reference: evaluate rules one by one; winner by (tier, priority,
    confidence)."""
    act = {a: bool(f) for a, f in zip(atom_names, fired_row)}
    conf = {a: float(c) for a, c in zip(atom_names, conf_row)}
    best = None
    for i, rule in enumerate(cfg.rules):
        if not rule.condition.evaluate(act):
            continue
        pos_conf = max((conf[a] for a in rule.condition.atoms()
                        if act.get(a)), default=0.0)
        key = (rule.tier, rule.priority, round(pos_conf, 6), )
        if best is None or key > best[0]:
            best = (key, rule.name)
    return best[1] if best else "__default__"


@st.composite
def rule_sets(draw, multi_term=False):
    """Random policies over ``ATOMS``; ``multi_term`` adds conditions of
    2–3 DNF terms (OR of ANDs, NOT literals, terms with no positive
    literal)."""
    n = draw(st.integers(1, 5))
    lines = [f"SIGNAL domain {a} {{}}" for a in ATOMS]
    for i in range(n):
        a = draw(st.sampled_from(ATOMS))
        b = draw(st.sampled_from(ATOMS))
        form = draw(st.integers(0, 4 if multi_term else 3))
        if form == 0:
            when = f'domain("{a}")'
        elif form == 1:
            when = f'domain("{a}") AND NOT domain("{b}")'
        elif form == 2:
            when = f'domain("{a}") OR domain("{b}")'
        elif form == 3:
            when = f'domain("{a}") AND domain("{b}")'
        else:
            terms = []
            for _ in range(draw(st.integers(2, 3))):
                lits = [f'domain("{x}")' for x in draw(
                    st.lists(st.sampled_from(ATOMS), max_size=2))]
                lits += [f'NOT domain("{x}")' for x in draw(
                    st.lists(st.sampled_from(ATOMS), max_size=2))]
                terms.append("(" + " AND ".join(
                    lits or [f'domain("{a}")']) + ")")
            when = " OR ".join(terms)
        pr = draw(st.integers(0, 300))
        tier = draw(st.integers(0, 2))
        lines.append(f'ROUTE r{i} {{ PRIORITY {pr} TIER {tier} '
                     f'WHEN {when} MODEL "m{i}" }}')
    lines.append('GLOBAL { default_model: "fallback" }')
    return "\n".join(lines)


@given(rule_sets(), st.integers(0, 2 ** 16))
@settings(max_examples=120, deadline=None)
def test_tensorized_matches_interpreter(text, seed):
    cfg = compile_text(text)
    tables = policy.build_tables(cfg)
    rng = np.random.default_rng(seed)
    b = 16
    fired = rng.random((b, len(tables.atom_names))) > 0.5
    conf = np.where(fired, rng.random((b, len(tables.atom_names))), 0.0) \
        .astype(np.float32)
    got = policy.route_names(tables, fired, conf)
    want = [interpreter(cfg, fired[i], conf[i], tables.atom_names)
            for i in range(b)]
    # ties in (tier, priority, confidence) may legitimately differ in
    # rule identity; compare the full sort key instead of the name
    def key_of(cfg, name, i):
        if name == "__default__":
            return None
        rule = next(r for r in cfg.rules if r.name == name)
        act = {a: bool(f) for a, f in
               zip(tables.atom_names, fired[i])}
        pc = max((float(c) for a, c in
                  zip(tables.atom_names, conf[i])
                  if a in rule.condition.atoms() and act.get(a)),
                 default=0.0)
        return (rule.tier, rule.priority, round(pc, 4))

    for i in range(b):
        assert key_of(cfg, got[i], i) == key_of(cfg, want[i], i), \
            (got[i], want[i])


def test_tier_beats_priority_and_confidence_breaks_ties():
    text = """
SIGNAL domain a {}
SIGNAL domain b {}
ROUTE low_tier_high_pri { PRIORITY 500 TIER 0 WHEN domain("a") MODEL "m1" }
ROUTE high_tier_low_pri { PRIORITY 10 TIER 1 WHEN domain("a") MODEL "m2" }
ROUTE same_pri_a { PRIORITY 100 WHEN domain("a") MODEL "m3" }
ROUTE same_pri_b { PRIORITY 100 WHEN domain("b") MODEL "m4" }
GLOBAL { default_model: "fallback" }
"""
    cfg = compile_text(text)
    tables = policy.build_tables(cfg)
    fired = np.array([[True, False], [False, True], [True, True]])
    conf = np.array([[0.9, 0.0], [0.0, 0.9], [0.6, 0.8]], np.float32)
    names = policy.route_names(tables, fired, conf)
    assert names[0] == "high_tier_low_pri"      # tier dominates priority
    # row 2: both same_pri rules fire at priority 100 but tier-1 rule wins
    assert names[2] == "high_tier_low_pri"
    # default when nothing fires
    names2 = policy.route_names(
        tables, np.zeros((1, 2), bool), np.zeros((1, 2), np.float32))
    assert names2 == ["__default__"]


def test_confidence_tie_break_at_high_tier_regression():
    """Regression (hypothesis-found): a scalarized tier*B²+pri*B+conf
    score loses the confidence tie-break to f32 rounding when tier > 0.
    The staged lexicographic argmax must get this right."""
    text = """
SIGNAL domain s0 {}
SIGNAL domain s1 {}
ROUTE r0 { PRIORITY 0 TIER 1 WHEN domain("s0") MODEL "m0" }
ROUTE r1 { PRIORITY 0 TIER 1 WHEN domain("s1") MODEL "m1" }
GLOBAL { default_model: "fallback" }
"""
    cfg = compile_text(text)
    tables = policy.build_tables(cfg)
    fired = np.array([[True, True]])
    conf = np.array([[0.0708, 0.0939]], np.float32)  # tiny margin
    assert policy.route_names(tables, fired, conf) == ["r1"]


def test_confidence_tie_break_within_priority():
    text = """
SIGNAL domain a {}
SIGNAL domain b {}
ROUTE ra { PRIORITY 100 WHEN domain("a") MODEL "m1" }
ROUTE rb { PRIORITY 100 WHEN domain("b") MODEL "m2" }
"""
    cfg = compile_text(text)
    tables = policy.build_tables(cfg)
    fired = np.array([[True, True]])
    conf = np.array([[0.3, 0.9]], np.float32)
    assert policy.route_names(tables, fired, conf) == ["rb"]
    conf = np.array([[0.9, 0.3]], np.float32)
    assert policy.route_names(tables, fired, conf) == ["ra"]


# ---- literal-index layout against the dense (term, atom) formula ----------

def dense_oracle(cfg, fired, conf):
    """The dense-mask evaluator, in numpy, as a reference only: (T, A)
    positive/negative literal masks, literal counts by matmul, a
    term -> rule max, then the staged (tier, priority, confidence)
    argmax.  -> (route idx (B,), score (B,))."""
    atoms = sorted(cfg.signals)
    aidx = {a: i for i, a in enumerate(atoms)}
    pos, neg, term_rule = [], [], []
    for ri, rule in enumerate(cfg.rules):
        for p, n in to_dnf_atoms(rule.condition):
            pos.append(np.isin(np.arange(len(atoms)),
                               [aidx[a] for a in p]).astype(np.float32))
            neg.append(np.isin(np.arange(len(atoms)),
                               [aidx[a] for a in n]).astype(np.float32))
            term_rule.append(ri)
    pos, neg = np.stack(pos), np.stack(neg)
    f = fired.astype(np.float32)
    conf = conf.astype(np.float32)
    term_ok = (f @ pos.T >= pos.sum(axis=1)) & (f @ neg.T <= 0.0)
    term_conf = np.where(
        term_ok, np.where(pos[None] > 0, conf[:, None, :],
                          np.float32(0)).max(axis=-1), np.float32(0))
    b, r = f.shape[0], len(cfg.rules)
    rule_ok = np.zeros((b, r), bool)
    rule_conf = np.zeros((b, r), np.float32)
    for t, ri in enumerate(term_rule):
        rule_ok[:, ri] |= term_ok[:, t]
        rule_conf[:, ri] = np.maximum(rule_conf[:, ri], term_conf[:, t])
    tier = np.asarray([x.tier for x in cfg.rules], np.float32)[None]
    pri = np.asarray([x.priority for x in cfg.rules], np.float32)[None]
    t = np.where(rule_ok, tier, -np.inf)
    m1 = rule_ok & (t >= t.max(axis=-1, keepdims=True))
    p = np.where(m1, pri, -np.inf)
    m2 = m1 & (p >= p.max(axis=-1, keepdims=True))
    c = np.where(m2, np.clip(rule_conf, 0.0, 1.0), -np.inf) \
        .astype(np.float32)
    best = c.argmax(axis=-1)
    none = ~rule_ok.any(axis=-1)
    return (np.where(none, r, best),
            np.where(none, -np.inf, c[np.arange(b), best]))


def random_policy(rng, n_atoms=6, n_rules=12):
    """A policy whose rules have 1–3 terms each (uneven OR), NOT
    literals, a term with no positive literal, and a rule that can
    never fire; priorities, tiers and confidences drawn from few values
    so the staged argmax meets ties."""
    names = [f"s{i}" for i in range(n_atoms)]
    lines = [f"SIGNAL domain {a} {{}}" for a in names]

    def lit(a, negated=False):
        return ("NOT " if negated else "") + f'domain("{a}")'

    for i in range(n_rules):
        terms = []
        for _ in range(int(rng.integers(1, 4))):
            k_pos, k_neg = int(rng.integers(0, 4)), int(rng.integers(0, 3))
            lits = [lit(a) for a in rng.choice(names, k_pos, replace=False)]
            lits += [lit(a, True)
                     for a in rng.choice(names, k_neg, replace=False)]
            terms.append("(" + " AND ".join(
                lits or [lit(names[i % n_atoms])]) + ")")
        when = " OR ".join(terms)
        lines.append(f'ROUTE r{i} {{ PRIORITY {int(rng.integers(0, 3)) * 100}'
                     f' TIER {int(rng.integers(0, 2))} WHEN {when} '
                     f'MODEL "m{i}" }}')
    lines.append(f'ROUTE only_neg {{ PRIORITY 50 WHEN {lit("s0", True)} '
                 f'OR ({lit("s1")} AND {lit("s2", True)}) MODEL "n" }}')
    lines.append(f'ROUTE never {{ PRIORITY 999 TIER 3 WHEN {lit("s1")} AND '
                 f'{lit("s1", True)} MODEL "x" }}')
    lines.append('GLOBAL { default_model: "fallback" }')
    return compile_text("\n".join(lines))


def random_activations(rng, n_atoms, b=32):
    fired = rng.random((b, n_atoms)) > 0.5
    conf = np.where(fired, rng.integers(1, 5, (b, n_atoms)) / 4.0, 0.0)
    return fired, conf.astype(np.float32)


def assert_bitwise(cfg, tables, fired, conf):
    idx, score = policy.evaluate_indices(tables, fired, conf)
    want_idx, want_score = dense_oracle(cfg, fired, conf)
    np.testing.assert_array_equal(idx, want_idx)
    assert score.dtype == np.float32
    assert np.array_equal(score.view(np.int32),
                          want_score.astype(np.float32).view(np.int32))


@pytest.mark.parametrize("seed", range(8))
def test_literal_index_tables_match_dense_oracle_bitwise(seed):
    rng = np.random.default_rng(seed)
    cfg = random_policy(rng)
    tables = policy.build_tables(cfg)
    stats = tables.stats()
    assert stats["M"] == max(len(to_dnf_atoms(r.condition))
                             for r in cfg.rules)
    assert stats["Kn"] >= 1 and stats["terms"] == sum(
        len(to_dnf_atoms(r.condition)) for r in cfg.rules)
    fired, conf = random_activations(rng, len(tables.atom_names))
    assert_bitwise(cfg, tables, fired, conf)
    # the contradictory rule never wins, even where s1 fires
    never = tables.rule_names.index("never")
    idx, _ = policy.evaluate_indices(tables, fired, conf)
    assert (idx != never).all() and fired[:, 1].any()


@given(rule_sets(multi_term=True), st.integers(0, 2 ** 16))
@settings(max_examples=120, deadline=None)
def test_multi_term_policies_match_dense_oracle_bitwise(text, seed):
    cfg = compile_text(text)
    tables = policy.build_tables(cfg)
    fired, conf = random_activations(np.random.default_rng(seed),
                                     len(tables.atom_names), b=16)
    assert_bitwise(cfg, tables, fired, conf)


def one_atom_policy(n):
    lines = [f"SIGNAL domain t{i} {{}}" for i in range(n)]
    lines += [f'ROUTE r{i} {{ PRIORITY {i % 7} WHEN domain("t{i}") '
              f'MODEL "m" }}' for i in range(n)]
    return compile_text("\n".join(lines))


def test_tables_stay_sparse_at_2000_one_atom_rules():
    cfg = one_atom_policy(2000)
    tables = policy.build_tables(cfg)
    stats = tables.stats()
    assert stats == {"rules": 2000, "terms": 2000, "literals": 2000,
                     "M": 1, "Kp": 1, "Kn": 0, "pad_share": 0.0}
    m, kp, kn = stats["M"], stats["Kp"], stats["Kn"]
    cap = 2000 * (m * kp + m * kn + m)
    arrays = [v for v in vars(tables).values() if isinstance(v, np.ndarray)]
    arrays += list(tables.as_jax().values())
    assert len(arrays) >= 10
    assert max(a.size for a in arrays) <= cap
    rng = np.random.default_rng(7)
    fired, conf = random_activations(rng, 2000, b=8)
    assert_bitwise(cfg, tables, fired, conf)


def test_pad_share_counts_padding_of_uneven_rules():
    cfg = compile_text("""
SIGNAL domain a {}
SIGNAL domain b {}
SIGNAL domain c {}
ROUTE wide { PRIORITY 1 WHEN (domain("a") AND domain("b")) OR domain("c") OR NOT domain("a") MODEL "m" }
ROUTE one { PRIORITY 2 WHEN domain("b") MODEL "m" }
""")
    stats = policy.build_tables(cfg).stats()
    # 2 rules x 3 terms x (Kp 2 + Kn 1) = 18 slots, 5 of them literals
    assert stats == {"rules": 2, "terms": 4, "literals": 5, "M": 3,
                     "Kp": 2, "Kn": 1, "pad_share": 1.0 - 5 / 18}


def test_telemetry_reports_policy_shape():
    from repro.serving.router import RouterService
    text = """
SIGNAL keyword hi { keywords: ["hello"] }
SIGNAL keyword bye { keywords: ["goodbye"] }
ROUTE greet { PRIORITY 2 WHEN keyword("hi") AND NOT keyword("bye") MODEL "m" }
ROUTE part { PRIORITY 1 WHEN keyword("bye") OR keyword("hi") MODEL "m" }
GLOBAL { default_model: "m" }
"""
    svc = RouterService(text, load_backends=False)
    assert svc.telemetry()["policy"] == {
        "rules": 2, "terms": 3, "literals": 4, "M": 2, "Kp": 1, "Kn": 1,
        "pad_share": 1.0 - 4 / 8}
    assert svc.route(["hello", "goodbye", "hello goodbye"]) == [
        "greet", "part", "part"]
