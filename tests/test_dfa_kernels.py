"""DFA device-kernel parity: all three evaluation tiers (flat gather,
dense one-hot MXU, block-diagonal one-hot MXU) must agree with the
host automaton on mixed pattern banks — the blocked tier is only
reachable past the dense size gate in production, so it needs direct
coverage (r4 review finding)."""
import numpy as np

from istio_tpu.ops import bytes_ops
from istio_tpu.ops.regex_dfa import (compile_regex, dfa_matches_host,
                                     pack_dfas, pack_dfas_classes,
                                     pack_dfas_onehot,
                                     pack_dfas_onehot_blocked)

PATS = ([f"^/api/v{k}/" for k in range(6)] +
        [r"items/[0-9]+", r"^/x$", r"a+b*c", r"(foo|bar)baz"])
SUBJECTS = [b"/api/v3/items/77", b"/x", b"/xx", b"", b"aac", b"abc",
            b"ac", b"/items/123", b"zzz", b"/api/v9/x", b"foobaz",
            b"xbarbazy"]


def _tensors():
    L = 32
    data = np.zeros((len(SUBJECTS), L), np.uint8)
    lens = np.zeros(len(SUBJECTS), np.int32)
    for i, s in enumerate(SUBJECTS):
        data[i, :len(s)] = np.frombuffer(s, np.uint8)
        lens[i] = len(s)
    return data, lens


def test_all_dfa_tiers_match_host_oracle():
    dfas = [compile_regex(p) for p in PATS]
    data, lens = _tensors()
    want = np.asarray([[dfa_matches_host(d, s) for d in dfas]
                       for s in SUBJECTS])

    trans, accept = pack_dfas(dfas)
    gather = np.asarray(bytes_ops.dfa_match_many(data, lens, trans,
                                                 accept))
    np.testing.assert_array_equal(gather, want)

    classes = pack_dfas_classes(dfas)
    dense = np.asarray(bytes_ops.dfa_match_many_onehot(
        data, lens, pack_dfas_onehot(dfas, classes)))
    np.testing.assert_array_equal(dense, want)

    blocked = np.asarray(bytes_ops.dfa_match_many_onehot_blocked(
        data, lens, pack_dfas_onehot_blocked(dfas, classes)))
    np.testing.assert_array_equal(blocked, want)


PREFIXES = [b"", b"/", b"/api/v3/", b"/api/v9/x", b"/x", b"/xx", b"aac",
            b"foobazz", b"z" * 32, b"/api/v3/items/77" + b"0" * 17]


def test_prefix_group_columns_are_the_single_prefix_match():
    # an empty prefix, one as long as its subject, one as wide as the
    # plane and one wider
    data, lens = _tensors()
    assert max(map(len, PREFIXES)) > data.shape[1]
    many = np.asarray(bytes_ops.prefix_match_many(data, lens, PREFIXES))
    want = np.asarray([[s.startswith(p) for p in PREFIXES]
                       for s in SUBJECTS])
    np.testing.assert_array_equal(many, want)
    for i, p in enumerate(PREFIXES):
        np.testing.assert_array_equal(
            many[:, i], np.asarray(bytes_ops.prefix_match(data, lens, p)))
    assert many.sum() > len(SUBJECTS)      # more than the empty prefix
    # a narrowed plane (fused.narrow_batch) keeps the true lengths
    short = lens <= 8
    narrow = np.asarray(bytes_ops.prefix_match_many(
        data[short, :8], lens[short], PREFIXES))
    np.testing.assert_array_equal(narrow, want[short])
    assert 0 < short.sum() < len(SUBJECTS)


# ---- the candidate tier: a bank past both one-hot tiers, every
# automaton guarded by one value of one id-equality --------------------

import pytest  # noqa: E402

from istio_tpu.attribute.bag import bag_from_mapping  # noqa: E402
from istio_tpu.attribute.types import ValueType  # noqa: E402
from istio_tpu.compiler import ruleset as ruleset_mod  # noqa: E402
from istio_tpu.compiler import tensor_expr  # noqa: E402
from istio_tpu.compiler.layout import Tensorizer  # noqa: E402
from istio_tpu.compiler.ruleset import Rule, compile_ruleset  # noqa: E402
from istio_tpu.expr.checker import AttributeDescriptorFinder  # noqa: E402
from istio_tpu.expr.parser import parse  # noqa: E402

ROUTE_FINDER = AttributeDescriptorFinder({
    "destination.service": ValueType.STRING,
    "request.path": ValueType.STRING})
N_HOSTS, PER_HOST, CAP = 40, 10, 64


def _pattern(r: int) -> str:
    """Route-table regexes, full-match, each naming its own r."""
    return (f"^(/api/v{r % 9}/r{r}/items/[0-9]+)$",
            f"^(/r{r}/(products|reviews)/[0-9]+/v{r % 4})$",
            f"^(/v{r % 3}/t/[a-z0-9-]+/r{r}(/.*)?)$")[r % 3]


def _path(r: int, rng) -> str:
    n = int(rng.integers(1, 10 ** int(rng.integers(1, 9))))
    return (f"/api/v{r % 9}/r{r}/items/{n}",
            f"/r{r}/reviews/{n}/v{r % 4}",
            f"/v{r % 3}/t/tenant-{n}/r{r}")[r % 3]


HEALTH, SHARED = "^(/healthz)$", "^(/api/v1/.*)$"


def _rules(kind: str = "table") -> list[Rule]:
    """`table`: every block behind its host. `mixed`: the table, one
    mesh-wide block any host's request reaches and one pattern two
    hosts share. `unguarded`: no block names a host."""
    guard = "" if kind == "unguarded" else 'destination.service == "h{}" && '
    rules = [Rule(name=f"r{r}", match=(
        guard.format(r % N_HOSTS)
        + f'"{_pattern(r)}".matches(request.path)'))
        for r in range(N_HOSTS * PER_HOST)]
    if kind == "mixed":
        rules.append(Rule(name="health",
                          match=f'"{HEALTH}".matches(request.path)'))
        rules += [Rule(name=f"shared{h}", match=(
            f'destination.service == "h{h}" && '
            f'"{SHARED}".matches(request.path)')) for h in (0, 1)]
    return rules


def _bags(rows: int = 256, seed: int = 2147485001) -> list:
    """Random subjects: a block of the row's own host, of another
    host, junk, empty, absent, at and past the cap; a row with no
    destination.service (the guard errs) and one with an unknown."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(rows):
        s = int(rng.integers(N_HOSTS))
        r = s + N_HOSTS * int(rng.integers(PER_HOST))
        kind = i % 8
        d = {"destination.service": f"h{s}", "request.path": _path(r, rng)}
        if kind == 1:
            d["request.path"] = _path((r + 1) % (N_HOSTS * PER_HOST), rng)
        elif kind == 2:
            d["request.path"] = "/" + "x" * int(rng.integers(0, 30))
        elif kind == 3:
            d["request.path"] = ""
        elif kind == 4:
            del d["request.path"]
        elif kind == 5:    # matches, but reaches the cap: undecidable
            d["request.path"] = f"/v{r % 3}/t/{'a' * CAP}/r{r}"
        elif kind == 6 and i % 16 == 6:
            del d["destination.service"]
        elif kind == 6:
            d["destination.service"] = "nobody"
        out.append(bag_from_mapping(d))
    # the mesh-wide block under any host and none, the shared pattern
    # under both its hosts and a third
    out += [bag_from_mapping(d) for d in (
        {"destination.service": "h5", "request.path": "/healthz"},
        {"request.path": "/healthz"},
        {"destination.service": "h0", "request.path": "/api/v1/a"},
        {"destination.service": "h1", "request.path": "/api/v1/b/c"},
        {"destination.service": "h2", "request.path": "/api/v1/a"})]
    return out


@pytest.fixture(scope="module", params=["table", "mixed"])
def guarded_and_whole(request):
    """The same rules compiled twice: the bank under its candidate
    tier, and with the guard withheld (today's whole-bank gather)."""
    prog = compile_ruleset(_rules(request.param), ROUTE_FINDER,
                           max_str_len=CAP)
    keep = ruleset_mod._bank_guard
    ruleset_mod._bank_guard = lambda atoms, guards: None
    try:
        whole = compile_ruleset(_rules(request.param), ROUTE_FINDER,
                                max_str_len=CAP)
    finally:
        ruleset_mod._bank_guard = keep
    bags = _bags()
    planes = []
    for p in (prog, whole):
        batch = Tensorizer(p.layout, p.interner).tensorize(bags)
        planes.append([np.asarray(x) for x in p(batch)])
    return prog, whole, bags, planes


@pytest.mark.parametrize("kind, banks", [
    ("table", [("candidates", N_HOSTS * PER_HOST, PER_HOST)]),
    # the shared pattern is one automaton, a candidate under both its
    # hosts; the mesh-wide one is a bank of its own that every row scans
    ("mixed", [("candidates", N_HOSTS * PER_HOST + 1, PER_HOST + 1),
               ("onehot", 1, 1)]),
    ("unguarded", [("gather", N_HOSTS * PER_HOST, N_HOSTS * PER_HOST)])])
def test_bank_tier_follows_what_the_ruleset_shows(kind, banks):
    prog = compile_ruleset(_rules(kind), ROUTE_FINDER, max_str_len=CAP)
    assert not prog.host_fallback
    got = prog.geometry["dfa_banks"]
    assert [(b["tier"], b["automata"], b["candidates"]) for b in got] \
        == banks
    assert all(b["subject"] == "$request.path" and b["bytes"] > 0
               for b in got)
    assert prog.geometry["n_dfa_groups"] == 1
    # the candidate bank rides the step's arguments
    assert any(k.startswith("dfa0_") for k in prog.params) \
        == (kind != "unguarded")


def test_small_guarded_bank_keeps_its_onehot_tier():
    rules = [Rule(name=f"r{r}", match=(
        f'destination.service == "h{r}" && '
        f'"/(products|reviews)/[0-9]+/v{r % 4}".matches(request.path)'))
        for r in range(100)]
    bank, = compile_ruleset(rules, ROUTE_FINDER).geometry["dfa_banks"]
    assert bank["tier"] == "onehot" and bank["automata"] == 4


def test_mixed_bank_answers_the_shared_and_the_mesh_wide_block():
    prog = compile_ruleset(_rules("mixed"), ROUTE_FINDER, max_str_len=CAP)
    bags = _bags()[-5:]
    batch = Tensorizer(prog.layout, prog.interner).tensorize(bags)
    matched = np.asarray(prog(batch)[0])
    n = N_HOSTS * PER_HOST                 # health, shared0, shared1
    assert matched[:, n:n + 3].tolist() == [
        [True, False, False], [True, False, False],
        [False, True, False], [False, False, True],
        [False, False, False]]
    assert not matched[:, :n].any()


@pytest.mark.parametrize("plane", ["matched", "not_matched", "err"])
def test_candidate_tier_leaves_every_rule_plane_as_it_was(
        guarded_and_whole, plane):
    prog, whole, _, (got, want) = guarded_and_whole
    assert prog.geometry["dfa_banks"][0]["tier"] == "candidates"
    assert [b["tier"] for b in whole.geometry["dfa_banks"]] == ["gather"]
    i = ("matched", "not_matched", "err").index(plane)
    np.testing.assert_array_equal(got[i], want[i])
    assert 0 < got[i].sum() < got[i].size      # the plane says something


def test_candidate_scan_equals_the_whole_bank_where_the_guard_holds(
        guarded_and_whole):
    prog, _, bags, _ = guarded_and_whole
    n = N_HOSTS * PER_HOST
    patterns = [_pattern(r) for r in range(n)]
    dfas = [compile_regex(p) for p in patterns]
    ctx = tensor_expr._Ctx(prog.layout, prog.interner, ROUTE_FINDER)
    col = prog.layout.slot_of("destination.service")
    ids = [(prog.interner.intern(f"h{r % N_HOSTS}"),) for r in range(n)]
    cand = tensor_expr.compile_dfa_group(
        parse("request.path"), patterns, dfas, ctx, guard=(col, ids))
    bank = tensor_expr.compile_dfa_group(
        parse("request.path"), patterns, dfas, ctx)
    assert [b["tier"] for b in cand.banks + bank.banks] \
        == ["candidates", "gather"]
    assert cand.order == bank.order == list(range(n))
    batch = Tensorizer(prog.layout, prog.interner).tensorize(bags)
    val, ee = (np.asarray(x) for x in cand(batch, cand.params))
    val_w, ee_w = (np.asarray(x) for x in bank(batch))
    host = [b.get("destination.service")[0] for b in bags]
    holds = np.asarray([[h == f"h{r % N_HOSTS}" for r in range(n)]
                        for h in host])
    np.testing.assert_array_equal(val[holds], val_w[holds])
    np.testing.assert_array_equal(ee[holds], ee_w[holds])
    assert val[holds].any() and ee[holds].any() and not val[~holds].any()


def test_candidate_kernel_scans_a_rows_own_automata():
    from istio_tpu.ops.regex_dfa import pack_dfas_tiered

    n = N_HOSTS * PER_HOST
    dfas = [compile_regex(_pattern(r)) for r in range(n)]
    tiers = pack_dfas_tiered(dfas, [(r % N_HOSTS,) for r in range(n)])
    assert tiers["trans"] is None and tiers["cand"]["rest"] is None
    c = tiers["cand"]
    assert c["k"] == PER_HOST and c["local"].dtype == np.int8
    assert c["cand"].shape == (N_HOSTS + 1, PER_HOST)
    rng = np.random.default_rng(7)
    hosts = rng.integers(0, N_HOSTS + 1, 64)        # N_HOSTS: no value
    subjects = [_path(int(h) % N_HOSTS + N_HOSTS * int(j), rng).encode()
                for h, j in zip(hosts, rng.integers(0, PER_HOST, 64))]
    subjects[3] = b""
    data, lens = bytes_ops.pad_bytes(subjects, CAP)
    got = np.asarray(bytes_ops.dfa_match_candidates(
        data, lens, c["cand"][hosts], c["local"], c["accept"],
        c["class_of"], c["n_states_max"], c["width"]))
    want = np.asarray(bytes_ops.dfa_match_many(data, lens,
                                               *pack_dfas(dfas)))
    assert got.shape == (64, PER_HOST) and got.sum() >= 32
    for b, h in enumerate(hosts):
        mine = [r for r in range(n) if r % N_HOSTS == h]
        assert list(c["cand"][h, :len(mine)]) == mine
        assert list(got[b, :len(mine)]) == list(want[b, mine])
        assert not got[b, len(mine):].any()         # the dead automaton
    assert (hosts == N_HOSTS).any()


def test_a_bank_of_wide_automata_keeps_the_whole_bank_scan(monkeypatch):
    # the candidate scan lays K tables out beside every row: past
    # CANDIDATE_CELLS a row the guard is not used
    from istio_tpu.ops import regex_dfa

    n = N_HOSTS * PER_HOST
    dfas = [compile_regex(_pattern(r)) for r in range(n)]
    monkeypatch.setattr(regex_dfa, "CANDIDATE_CELLS", 1 << 12)
    tiers = regex_dfa.pack_dfas_tiered(
        dfas, [(r % N_HOSTS,) for r in range(n)])
    assert tiers["cand"] is None
    assert tiers["trans"].shape[0] == n
