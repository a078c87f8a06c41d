"""Ruleset compiler conformance: the batched DNF/matmul matcher must
agree with the oracle (3-valued: matched / not-matched / error) on every
boolean corpus predicate, evaluated as one batch over many bags.

Mirrors the reference pattern of one shared table driving multiple
engines (mixer/pkg/il/testing/tests.go consumed by compiler, interpreter
and evaluator tests).
"""
import numpy as np
import pytest

from istio_tpu.attribute.bag import bag_from_mapping
from istio_tpu.attribute.types import ValueType
from istio_tpu.compiler.layout import InternTable, Tensorizer
from istio_tpu.compiler.ruleset import (Rule, RuleSetProgram, compile_ruleset)
from istio_tpu.expr.checker import AttributeDescriptorFinder, TypeError_
from istio_tpu.expr.oracle import EvalError, OracleProgram
from istio_tpu.expr.parser import ParseError
from istio_tpu.testing.corpus import CORPUS, CORPUS_MANIFEST

FINDER = AttributeDescriptorFinder(CORPUS_MANIFEST)


def _bool_cases():
    """Corpus cases whose expression type-checks to BOOL."""
    out = []
    for c in CORPUS:
        if c.compile_err is not None:
            continue
        try:
            prog = OracleProgram(c.e, FINDER)
        except (ParseError, TypeError_):
            continue
        if prog.result_type == ValueType.BOOL:
            out.append(c)
    return out


BOOL_CASES = _bool_cases()
ALL_INPUTS = [c.input for c in CORPUS if c.compile_err is None]


def oracle_verdict(text, bag):
    try:
        v = bool(OracleProgram(text, FINDER).evaluate(bag))
        return (v, not v, False)
    except EvalError:
        return (False, False, True)


def eval_ruleset(prog: RuleSetProgram, bags):
    tz = Tensorizer(prog.layout, prog.interner)
    batch = tz.tensorize(bags)
    m, n, e = prog(batch)
    m, n, e = np.array(m), np.array(n), np.array(e)
    # overlay host-fallback rules exactly as the dispatcher does
    for ridx in prog.host_fallback:
        for b, bag in enumerate(bags):
            m[b, ridx], n[b, ridx], e[b, ridx] = prog.host_eval(ridx, bag)
    return m, n, e


def test_corpus_predicates_as_one_ruleset():
    """All boolean corpus predicates as one ruleset × all corpus inputs
    as one batch; every (rule, bag) cell must match the oracle."""
    rules = [Rule(name=f"r{i}", match=c.e) for i, c in enumerate(BOOL_CASES)]
    prog = compile_ruleset(rules, FINDER)
    bags = [bag_from_mapping(inp) for inp in ALL_INPUTS]
    m, n, e = eval_ruleset(prog, bags)
    for ridx, c in enumerate(BOOL_CASES):
        for b, inp in enumerate(ALL_INPUTS):
            want = oracle_verdict(c.e, bag_from_mapping(inp))
            got = (bool(m[b, ridx]), bool(n[b, ridx]), bool(e[b, ridx]))
            assert got == want, (
                f"rule {c.e!r} on input {inp!r}: got {got}, want {want} "
                f"(fallback={prog.fallback_reason.get(ridx)}")


def test_empty_match_always_matches():
    prog = compile_ruleset([Rule(name="r", match="")], FINDER)
    bags = [bag_from_mapping({}), bag_from_mapping({"a": 1})]
    m, n, e = eval_ruleset(prog, bags)
    assert m.all() and not n.any() and not e.any()


def test_const_false_never_matches():
    prog = compile_ruleset([Rule(name="r", match="false")], FINDER)
    m, n, e = eval_ruleset(prog, [bag_from_mapping({})])
    assert not m.any() and n.all() and not e.any()


def test_non_bool_match_rejected():
    with pytest.raises(TypeError_):
        compile_ruleset([Rule(name="r", match='"str"')], FINDER)


def test_short_circuit_error_suppression():
    """false && <error> must be not-matched, true || <error> matched —
    the M/N recurrences encode IL short-circuit (compiler.go:373/:354)."""
    rules = [
        Rule(name="a", match='a == 3 && as == "nope"'),   # a=2 → def false
        Rule(name="b", match='a == 2 || as == "nope"'),   # as absent, a=2
        Rule(name="c", match='a == 2 && as == "nope"'),   # as absent → err
        Rule(name="d", match='as == "x" || a == 2'),      # as absent → err
    ]
    prog = compile_ruleset(rules, FINDER)
    m, n, e = eval_ruleset(prog, [bag_from_mapping({"a": 2})])
    assert (bool(m[0, 0]), bool(e[0, 0])) == (False, False)
    assert (bool(m[0, 1]), bool(e[0, 1])) == (True, False)
    assert (bool(m[0, 2]), bool(e[0, 2])) == (False, True)
    assert (bool(m[0, 3]), bool(e[0, 3])) == (False, True)


PREFIXES = ["", "p", "pre", "prefix", "prefix-and-more", "q" * 200]
PREFIX_INPUTS = [{"as": "prefix"}, {"as": "pre"}, {"as": ""}, {},
                 {"as": "prefix-and-more" + "!" * 300}, {"as": "q" * 300},
                 {"as": "xprefix", "as2": "prefix"}]


@pytest.mark.parametrize("subject", ["as", '(as | "pre-set")',
                                     '(as | as2)'])
def test_constant_prefixes_over_one_subject_ride_one_group(subject):
    """startsWith(const) atoms over one subject evaluate as ONE group
    (tensor_expr.compile_prefix_group), each column with the single
    atom's semantics: an absent subject is an error, a truncated one
    still decidable, a prefix past the byte-slot cap the host's."""
    rules = [Rule(name=f"r{i}", match=f'{subject}.startsWith("{p}")')
             for i, p in enumerate(PREFIXES)]
    rules.append(Rule(name="both", match=(
        f'{subject}.startsWith("prefix") && {subject}.startsWith("p")')))
    rules.append(Rule(name="either", match=(
        f'{subject}.startsWith("prefix-and-more") || '
        f'{subject}.startsWith("")')))
    prog = compile_ruleset(rules, FINDER)
    g = prog.geometry
    assert (g["n_prefix_groups"], g["n_prefix_atoms"]) == (1, 5)
    assert g["n_gen_atoms"] == 0
    assert sorted(prog.host_fallback) == [5]      # the 200-byte prefix
    tiers = set(prog.atom_tier.values())
    assert tiers == {"prefix-pack"}
    bags = [bag_from_mapping(inp) for inp in PREFIX_INPUTS]
    m, n, e = eval_ruleset(prog, bags)
    seen = set()
    for ridx, rule in enumerate(rules):
        for b, bag in enumerate(bags):
            want = oracle_verdict(rule.match, bag)
            got = (bool(m[b, ridx]), bool(n[b, ridx]), bool(e[b, ridx]))
            assert got == want, (rule.match, PREFIX_INPUTS[b])
            seen.add(want)
    assert len(seen) >= 2


def test_namespace_masking():
    rules = [Rule(name="default", match="", namespace=""),
             Rule(name="ns1", match="", namespace="ns1"),
             Rule(name="ns2", match="", namespace="ns2")]
    prog = compile_ruleset(rules, FINDER)
    req = np.asarray([prog.namespace_id("ns1"), prog.namespace_id("other")])
    mask = np.asarray(prog.namespace_mask(req))
    assert mask.tolist() == [[True, True, False], [True, False, False]]


def test_attribute_masks():
    rules = [Rule(name="r0", match='a == 2 && request.header["host"] == "x"')]
    prog = compile_ruleset(rules, FINDER)
    names = prog.attr_names[0]
    assert "a" in names and "request.header" in names
    assert ("request.header", "host") in names
    cols = [prog.layout.slot_of("a"),
            prog.layout.derived_slot_of("request.header", "host")]
    assert all(prog.attr_mask[0, c] for c in cols)


def test_rule_guards_bound_the_rules_a_row_can_match():
    """The id-equality column every M-conjunction of a rule asserts
    (RuleSetProgram.guards): a row is a candidate of the rules guarded
    under the id it reads there and of the unguarded ones, of every
    rule where it lacks the column (a guard then errs, it does not
    miss)."""
    rules = [Rule(name="r0", match='as == "x" && a == 1'),
             Rule(name="r1", match='as == "y" && (a == 1 || b == 2)'),
             Rule(name="r2", match='(as == "x" && a == 1) || '
                                   '(as == "z" && b == 2)'),
             Rule(name="r3", match='b == 2'),            # no guard on `as`
             Rule(name="r4", match='as != "x" && b == 2'),   # NEQ: none
             Rule(name="r5", match='as == "x" || a == 1')]   # one side only
    prog = compile_ruleset(rules, FINDER)
    col, by_id, free = prog.guards
    assert col == prog.layout.slot_of("as")
    ids = {v: prog.interner.lookup(v) for v in "xyz"}
    assert by_id == {ids["x"]: [0, 2], ids["y"]: [1], ids["z"]: [2]}
    assert free == [3, 4, 5]
    bags = [bag_from_mapping(d) for d in (
        {"as": "x", "a": 1}, {"as": "y"}, {"as": "other"}, {"a": 1})]
    batch = Tensorizer(prog.layout, prog.interner).tensorize(bags)
    assert [prog.host_candidates(batch, b) for b in range(4)] == [
        [0, 2, 3, 4, 5], [1, 3, 4, 5], [3, 4, 5], [0, 1, 2, 3, 4, 5]]
    # a candidate set never loses a rule that matches
    m, _, _ = eval_ruleset(prog, bags)
    for b in range(4):
        assert set(np.flatnonzero(m[b])) <= set(
            prog.host_candidates(batch, b))


def test_atom_dedup_across_rules():
    rules = [Rule(name=f"r{i}", match=f'a == 2 && b == {i}') for i in range(20)]
    prog = compile_ruleset(rules, FINDER)
    # `a == 2` shared: 1 + 20 atoms, not 40
    assert prog.n_atoms == 21


def test_fallback_rule_is_isolated():
    """A rule needing host eval must not poison device rules."""
    rules = [Rule(name="dev", match="a == 2"),
             Rule(name="host", match='ar[as] == "v"')]  # dynamic key
    prog = compile_ruleset(rules, FINDER)
    assert 1 in prog.host_fallback and 0 not in prog.host_fallback
    m, n, e = eval_ruleset(prog, [bag_from_mapping(
        {"a": 2, "as": "k", "ar": {"k": "v"}})])
    assert bool(m[0, 0]) and bool(m[0, 1])


def test_large_ruleset_matches_oracle_spot():
    """1k synthetic rules in the Bookinfo style; spot-check agreement."""
    rng = np.random.default_rng(0)
    rules = []
    for i in range(1000):
        svc = f"svc{i % 50}.ns.svc.cluster.local"
        parts = [f'destination.service == "{svc}"']
        if i % 3 == 0:
            parts.append(f'source.namespace != "ns{i % 7}"')
        if i % 5 == 0:
            parts.append(f'request.header["cookie"] == "user{i % 11}"')
        rules.append(Rule(name=f"r{i}", match=" && ".join(parts)))
    prog = compile_ruleset(rules, FINDER)
    assert not prog.host_fallback
    bags = []
    for b in range(32):
        bag = {"destination.service":
               f"svc{rng.integers(0, 60)}.ns.svc.cluster.local",
               "source.namespace": f"ns{rng.integers(0, 8)}"}
        if rng.random() < 0.7:
            bag["request.header"] = {"cookie": f"user{rng.integers(0, 12)}"}
        bags.append(bag_from_mapping(bag))
    m, n, e = eval_ruleset(prog, bags)
    idx = rng.integers(0, 1000, size=60)
    for ridx in idx:
        for b in range(32):
            want = oracle_verdict(rules[ridx].match, bags[b])
            got = (bool(m[b, ridx]), bool(n[b, ridx]), bool(e[b, ridx]))
            assert got == want, (rules[ridx].match, b)
