"""ISSUE 6 unit coverage: the roofline model's shape-exactness, the
fused gather–compare fast path's oracle parity + compiled-away legacy
stage, bit-packed bank/mask round-trips, latency-tier byte-plane
specialization (identical verdicts across tier shapes), and the
in-step quota prewarm wiring (ADVICE r5: defined-but-never-called)."""
import numpy as np
import pytest

from istio_tpu.attribute.bag import bag_from_mapping
from istio_tpu.compiler import roofline
from istio_tpu.compiler.layout import Tensorizer
from istio_tpu.compiler.ruleset import Rule, compile_ruleset
from istio_tpu.expr.checker import AttributeDescriptorFinder
from istio_tpu.expr.oracle import EvalError, OracleProgram
from istio_tpu.ops import bytes_ops
from istio_tpu.ops.bytes_ops import pack_bits
from istio_tpu.ops.regex_dfa import compile_regex, dfa_matches_host
from istio_tpu.testing import workloads
from istio_tpu.testing.corpus import CORPUS_MANIFEST

FINDER = AttributeDescriptorFinder(CORPUS_MANIFEST)


# ---------------------------------------------------------------------------
# roofline model
# ---------------------------------------------------------------------------

def test_h2d_component_matches_tensorized_batch_exactly():
    engine = workloads.make_engine(n_rules=48, with_quota=True,
                                   jit=False)
    b = 32
    model = roofline.model_check_step(engine, b)
    ab = engine.tensorizer.tensorize(workloads.make_bags(b))
    actual = sum(int(np.asarray(a).nbytes) for a in (
        ab.ids, ab.present, ab.map_present, ab.str_bytes,
        ab.str_lens, ab.hash_ids))
    assert model.component("h2d_batch").bytes == actual


def test_index_tensor_bytes_match_live_params():
    engine = workloads.make_engine(n_rules=48, with_quota=False,
                                   jit=False)
    b = 16
    model = roofline.model_check_step(engine, b)
    params = engine.ruleset.params
    g = engine.ruleset.geometry
    want = sum(int(np.asarray(params[k]).nbytes)
               for k in ("conj_m_idx", "conj_n_idx"))
    got = model.component("match_rules").bytes \
        - b * g["n_rows"] * (2 * g["k_max"] + 3)
    assert got == want


def test_report_names_binding_resource():
    engine = workloads.make_engine(n_rules=32, with_quota=False,
                                   jit=False)
    model = roofline.model_check_step(engine, 64)
    # a step wall at ~the model's own roof time → device-bound label
    peaks = {"hbm_gbps": 1.0, "mxu_tops": 1.0, "label": "unit"}
    hbm_s = model.bytes_per_step / 1e9
    rep = model.report(hbm_s * 2, peaks)
    assert rep["bound"] in ("hbm", "mxu")
    assert 0 < rep["fraction_of_roof"] <= 1.0
    # a wall 1000× the roof → host-bound (dispatch/transport)
    rep = model.report(max(hbm_s, model.mxu_ops_per_step / 1e12)
                       * 1000, peaks)
    assert rep["bound"] == "host"


@pytest.mark.parametrize("kind,row", [
    ("TPU v5 lite", roofline.V5E_PEAKS),
    ("cpu", roofline.CPU_PEAKS),
    (None, roofline.CPU_PEAKS),      # default: this run's own device
])
def test_peaks_are_keyed_by_device_kind(kind, row):
    assert roofline.peaks_for(kind) is row


def test_unknown_device_kind_has_no_peaks():
    # no default row: a TPU that is not a v5e must not borrow its roof
    with pytest.raises(ValueError, match="TPU v4"):
        roofline.peaks_for("TPU v4")


def test_bench_fields_prefixed_and_fail_soft():
    engine = workloads.make_engine(n_rules=24, with_quota=False,
                                   jit=False)
    out = roofline.bench_fields(engine, 32, 1e-3, "zzz_")
    assert "zzz_fraction_of_roof" in out and "zzz_bound" in out
    # fail-soft: garbage engine yields an error field, never a raise
    out = roofline.bench_fields(object(), 32, 1e-3, "bad_")
    assert "bad_roofline_error" in out


# ---------------------------------------------------------------------------
# fused gather–compare fast path
# ---------------------------------------------------------------------------

EQ_RULES = [
    Rule(name="r0", match='as == "abc"'),
    Rule(name="r1", match='as != "xyz" && ab == true'),
    Rule(name="r2", match='ai == 42 || (as == "q" && ad == 1.5)'),
    Rule(name="r3", match=""),
]
MIXED_RULES = EQ_RULES + [
    Rule(name="r4", match='as.startsWith("ab")'),
    Rule(name="r5", match='as == as2 && ai == 7'),
]
INPUTS = [
    {"as": "abc", "ab": True, "ai": 42, "ad": 1.5, "as2": "abc"},
    {"as": "xyz", "ab": False, "ai": 7, "as2": "zzz"},
    {"as": "q", "ad": 1.5, "ai": 7, "as2": "q"},
    {"ab": True},
    {},
]


def _oracle(text, bag):
    try:
        v = bool(OracleProgram(text or "true", FINDER).evaluate(bag))
        return (v, not v, False)
    except EvalError:
        return (False, False, True)


def _run(prog, bags):
    tz = Tensorizer(prog.layout, prog.interner)
    m, n, e = prog(tz.tensorize(bags))
    return np.asarray(m), np.asarray(n), np.asarray(e)


def test_pure_eq_ruleset_compiles_away_legacy_stage():
    prog = compile_ruleset(EQ_RULES, FINDER)
    g = prog.geometry
    assert g["n_fused_conjs"] > 0
    assert g["n_legacy_conjs"] == 0
    assert not g["use_legacy"]
    bags = [bag_from_mapping(i) for i in INPUTS]
    m, n, e = _run(prog, bags)
    for ridx, rule in enumerate(EQ_RULES):
        for b, inp in enumerate(INPUTS):
            want = _oracle(rule.match, bag_from_mapping(inp))
            got = (bool(m[b, ridx]), bool(n[b, ridx]),
                   bool(e[b, ridx]))
            assert got == want, (rule.match, inp, got, want)


def test_mixed_ruleset_splits_conjunctions_and_matches_oracle():
    prog = compile_ruleset(MIXED_RULES, FINDER)
    g = prog.geometry
    assert g["n_fused_conjs"] > 0
    assert g["n_legacy_conjs"] > 0 and g["use_legacy"]
    assert g["n_fused_conjs"] + g["n_legacy_conjs"] == g["n_conjs"]
    bags = [bag_from_mapping(i) for i in INPUTS]
    m, n, e = _run(prog, bags)
    for ridx, rule in enumerate(MIXED_RULES):
        if ridx in prog.host_fallback:
            continue
        for b, inp in enumerate(INPUTS):
            want = _oracle(rule.match, bag_from_mapping(inp))
            got = (bool(m[b, ridx]), bool(n[b, ridx]),
                   bool(e[b, ridx]))
            assert got == want, (rule.match, inp, got, want)


# ---------------------------------------------------------------------------
# bit-packed lanes
# ---------------------------------------------------------------------------

def test_pack_unpack_bits_roundtrip():
    rng = np.random.default_rng(7)
    for shape in ((5,), (3, 37), (2, 4, 65), (1, 32), (6, 1)):
        a = rng.random(shape) < 0.3
        packed = pack_bits(a)
        assert packed.dtype == np.uint32
        assert packed.shape[-1] == (shape[-1] + 31) // 32
        back = np.asarray(bytes_ops.unpack_bits(packed, shape[-1]))
        np.testing.assert_array_equal(back, a)


def test_bitpacked_regex_list_bank_oracle_parity():
    """REGEX list actions drive the engine's packed (bit-lane) DFA
    banks; deny verdicts must match host automaton membership for
    whitelist AND blacklist polarity over a corpus of subjects."""
    from istio_tpu.models.policy_engine import (ListEntrySpec,
                                                PolicyEngine)

    patterns = [r"^/api/v[0-9]+/", r"\.internal$", r"(foo|bar)baz",
                r"^/healthz$"]
    rules = [Rule(name="white", match=""), Rule(name="black", match="")]
    engine = PolicyEngine(
        rules=rules, finder=FINDER,
        lists=[ListEntrySpec(rule=0, value_attr="as",
                             entries=patterns, blacklist=False,
                             entry_type="REGEX"),
               ListEntrySpec(rule=1, value_attr="as",
                             entries=patterns, blacklist=True,
                             entry_type="REGEX")])
    subjects = ["/api/v3/items", "db.internal", "foobaz", "/healthz",
                "/api/vx/items", "internal.db", "bazfoo", "", "zzz"]
    bags = [bag_from_mapping({"as": s}) for s in subjects]
    batch = engine.tensorizer.tensorize(bags)
    verdict = engine.check(batch, np.zeros(len(bags), np.int32))
    status = np.asarray(verdict.status)
    dfas = [compile_regex(p) for p in patterns]
    for i, s in enumerate(subjects):
        member = any(dfa_matches_host(d, s.encode()) for d in dfas)
        # blacklist hit → PERMISSION_DENIED(7) at rule 1; whitelist
        # miss → NOT_FOUND(5) at rule 0 (lowest rule index wins)
        want = 7 if member else 5
        assert int(status[i]) == want, (s, member, int(status[i]))


# ---------------------------------------------------------------------------
# latency-tier byte-plane specialization
# ---------------------------------------------------------------------------

def _tier_plan():
    from istio_tpu.runtime.config import SnapshotBuilder
    from istio_tpu.runtime.fused import build_fused_plan

    store = workloads.make_store(48, with_regex=True)
    snap = SnapshotBuilder(
        default_manifest=workloads.MESH_MANIFEST).build(store)
    return build_fused_plan(snap)


def test_str_tier_narrowing_identical_verdicts():
    """Bucket-specialization satellite: the SAME batch served through
    the narrowed latency tier and the full-width worst case must
    produce bit-identical packed verdicts."""
    plan = _tier_plan()
    lay = plan.engine.ruleset.layout
    if len(plan.str_tiers) < 2:
        pytest.skip("layout has no multi-tier byte planes")
    bags = workloads.make_bags(16, seed=3)
    batch = plan.engine.tensorizer.tensorize(bags)
    assert int(batch.str_lens.max()) <= plan.str_tiers[0], \
        "workload strings must fit the small tier for this test"
    ns = np.zeros(16, np.int32)
    narrowed = plan.narrow_batch(batch)
    assert narrowed.str_bytes.shape[2] == plan.str_tiers[0]
    assert narrowed.str_bytes.shape[2] < lay.max_str_len
    packed_narrow = plan.packed_check(batch, ns, observe=False)
    # force the full-width shape by disabling the tiers
    plan.str_tiers = (lay.max_str_len,)
    packed_full = plan.packed_check(batch, ns, observe=False)
    np.testing.assert_array_equal(packed_narrow, packed_full)


def test_str_tier_gated_off_by_long_byte_constant():
    """A compiled byte CONSTANT longer than the small tier makes
    narrowing unsound (slicing its row drops real tail bytes — e.g.
    the constant subject of endsWith), so str_tiers must not offer a
    tier below it, and verdicts must match the full-width path."""
    from istio_tpu.runtime.config import SnapshotBuilder
    from istio_tpu.runtime.fused import STR_TIER_MIN, build_fused_plan

    long_const = "A" * (STR_TIER_MIN + 5) + "end"
    store = workloads.make_store(8)
    store.set(("rule", "istio-system", "longconst-rule"), {
        "match": f'"{long_const}".endsWith(request.path)',
        "actions": [{"handler": "denyall.istio-system",
                     "instances": ["nothing.istio-system"]}]})
    snap = SnapshotBuilder(
        default_manifest=workloads.MESH_MANIFEST).build(store)
    plan = build_fused_plan(snap)
    assert min(plan.str_tiers) >= len(long_const)
    # the verdict the clipped-constant bug flipped: subject "end"
    # (fits any tier) must stay a suffix match of the long constant
    d = workloads.make_request_dicts(4, seed=2)
    d[1]["request.path"] = "end"
    d[3]["request.path"] = "nope"
    batch = plan.engine.tensorizer.tensorize(
        [bag_from_mapping(x) for x in d])
    assert plan.narrow_batch(batch).str_bytes.shape[2] \
        >= len(long_const)
    ns = np.zeros(4, np.int32)
    got = plan.packed_check(batch, ns, observe=False)
    plan.str_tiers = (plan.engine.ruleset.layout.max_str_len,)
    full = plan.packed_check(batch, ns, observe=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(full))


def test_str_tier_long_strings_keep_full_width():
    plan = _tier_plan()
    if len(plan.str_tiers) < 2:
        pytest.skip("layout has no multi-tier byte planes")
    lay = plan.engine.ruleset.layout
    d = workloads.make_request_dicts(4, seed=1)
    d[2]["request.path"] = "/" + "x" * (lay.max_str_len + 10)
    batch = plan.engine.tensorizer.tensorize(
        [bag_from_mapping(x) for x in d])
    assert plan.narrow_batch(batch).str_bytes.shape[2] == \
        lay.max_str_len


def test_prewarm_warms_every_tier_shape():
    plan = _tier_plan()
    if len(plan.str_tiers) < 2:
        pytest.skip("layout has no multi-tier byte planes")
    batches = plan._prewarm_batches(8)
    widths = {plan.narrow_batch(b).str_bytes.shape[2]
              for b in batches}
    assert widths == set(plan.str_tiers)


# ---------------------------------------------------------------------------
# in-step quota prewarm wiring
# ---------------------------------------------------------------------------

def test_prewarm_instep_wired_on_publish():
    """ADVICE r5: fused.prewarm_instep existed but nothing called it.
    A quota_in_step server must have the merged check+alloc program
    compiled (the _instep_packer populated) after a config publish,
    without any quota-carrying traffic."""
    from istio_tpu.runtime import MemStore, RuntimeServer, ServerArgs

    s = MemStore()
    s.set(("handler", "istio-system", "mq"), {
        "adapter": "memquota",
        "params": {"quotas": [{"name": "rq.istio-system",
                               "max_amount": 40,
                               "valid_duration_s": 10.0}]}})
    s.set(("instance", "istio-system", "rq"), {
        "template": "quota",
        "params": {"dimensions": {"user": 'source.user | "anon"'}}})
    s.set(("rule", "istio-system", "rq-rule"), {
        "match": "", "actions": [{"handler": "mq",
                                  "instances": ["rq"]}]})
    srv = RuntimeServer(s, ServerArgs(
        fused=True, max_batch=8, buckets=(8,), quota_in_step=True,
        rulestats_drain_s=0))
    try:
        assert srv.instep_quota_target() is not None
        # the publish hook path (synchronous for swaps) — drive it
        # directly so the assertion doesn't race the init-time
        # background warm
        srv.prewarm_instep()
        assert srv.controller.dispatcher.fused._instep_packer \
            is not None
    finally:
        srv.close()
