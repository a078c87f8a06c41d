"""The `routematch10k` deployment (benchmark/configs/routematch.py,
BASELINE config 3) through the served entry: a route table's match
blocks as Mixer rules, every block with its own regex, at the
configuration's smoke sizes (both DFA banks block-diagonal one-hot) and
at a size whose request-line bank is past both one-hot tiers, so the
compiler scans each row's candidate automata.

The configuration's files are the benchmark's; they are loaded by path
as benchmark/run.py loads them.
"""
import collections
import importlib.util
import json
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import pytest

from istio_tpu.api import MixerClient
from istio_tpu.api.native_server import NativeMixerServer
from istio_tpu.api.wire import LazyWireBag, bag_to_compressed
from istio_tpu.attribute.bag import bag_from_mapping
from istio_tpu.attribute.global_dict import GLOBAL_MANIFEST
from istio_tpu.runtime import RuntimeServer, ServerArgs, monitor
from istio_tpu.runtime.batcher import pad_to_bucket

CONFIGS = Path(__file__).resolve().parent.parent / "benchmark" / "configs"
ROWS, BUCKET, WIRE = 512, 256, 64
SEED = 2147484471           # the driver's seeds pass 2**31
# sizes over the smoke ones -> the tier each subject's bank must take
SCALES = {
    "smoke": ({}, {"$request.path": ("onehot-blocked", 225),
                   "cookie": ("onehot-blocked", 75)}),
    "candidates": ({"rules": 1000, "services": 100},
                   {"$request.path": ("candidates", 8),
                    "cookie": ("onehot-blocked", 250)}),
}


def _load(name: str, **over):
    """(smoke sizes, generator module) of one benchmark configuration."""
    sizes = json.loads((CONFIGS / f"{name}.json").read_text())
    sizes.update(sizes["smoke"])
    sizes.update(over)
    spec = importlib.util.spec_from_file_location(
        f"bench_config_{sizes['module']}",
        CONFIGS / f"{sizes['module']}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return sizes, module


def _server(sizes: dict, config) -> RuntimeServer:
    return RuntimeServer(config.make_store(sizes), ServerArgs(
        default_manifest={k: GLOBAL_MANIFEST[k] for k in sizes["manifest"]},
        buckets=tuple(sizes["buckets"]), max_batch=sizes["max_batch"],
        initial_prewarm=False))


def _at_the_cap(sizes: dict, config) -> dict:
    """A request line of 128 bytes or more that its block's pattern
    full-matches: undecidable on the 128-byte plane, so the length
    split serves it on the wide one."""
    r = next(r for r in range(sizes["rules"])
             if config.family_of(sizes, r) == 3 and r % 3 == 0)   # denies
    return {"destination.service":
            config.host_of(sizes, r % sizes["services"]),
            "source.namespace": "ns1", "request.method": "GET",
            "request.path": f"/v{r % 3}/t/{'a' * 128}/r{r}",
            "request.headers": {":authority": "x",
                                "x-version": f"v{r % 5}"}}


@pytest.fixture(scope="module", params=list(SCALES))
def served(request):
    """ROWS seeded requests through the pump's own entry, a padded
    bucket at a time, the first WIRE of them and one at the byte cap
    through the socket too (native front, MixerClient: a CPU step a
    handful of rows is what makes more of them slow), with everything
    the cases compare."""
    over, tiers = SCALES[request.param]
    sizes, config = _load("routematch10k", **over)
    requests = config.make_requests(sizes, ROWS, SEED)
    requests.append(_at_the_cap(sizes, config))
    spans0 = monitor.stage_baseline()
    srv = _server(sizes, config)
    native = NativeMixerServer(srv, max_batch=sizes["max_batch"])
    client = MixerClient(f"127.0.0.1:{native.start()}",
                         enable_check_cache=False)
    try:
        plan = srv.controller.dispatcher.fused
        assert plan is not None and plan.native is not None
        ruleset = plan.engine.ruleset
        spans = monitor.latency_snapshot(since=spans0)["spans"]
        wire_bags = [srv.preprocess(LazyWireBag(
            bag_to_compressed(d).SerializeToString()))
            for d in requests[:ROWS]]
        got = []
        for lo in range(0, ROWS, BUCKET):
            padded = pad_to_bucket(wire_bags[lo:lo + BUCKET], (BUCKET,))
            got += srv.check_batch_preprocessed(padded)[:BUCKET]
        with ThreadPoolExecutor(max_workers=16) as pool:
            replies = list(pool.map(
                client.check, requests[:WIRE] + requests[ROWS:]))
        oracle = srv.controller.dispatcher.check_host_oracle(
            [bag_from_mapping(d) for d in requests])
    finally:
        client.close()
        native.stop()
        srv.close()
    specs = config.rule_specs(sizes)
    by_host = collections.defaultdict(list)
    for r, spec in enumerate(specs):
        by_host[spec["host"]].append((r, spec))
    return {
        "sizes": sizes, "config": config, "requests": requests,
        "specs": specs, "by_host": by_host,
        "tiers": tiers, "ruleset": ruleset, "spans": spans,
        "got": [int(r.status_code) for r in got],
        "wire": [int(r.precondition.status.code) for r in replies],
        "oracle": [int(r.status_code) for r in oracle],
        "expected": [config.reference(sizes)(d) for d in requests]}


def _matching(served, request) -> list[int]:
    """Indices of the blocks of the request's host that match it, by
    the module's own data."""
    return [r for r, spec in served["by_host"].get(
        request["destination.service"], ())
        if served["config"]._block_matches(spec["match"], request)]


def test_store_compiles_to_two_dfa_groups_and_no_host_rule(served):
    ruleset, sizes = served["ruleset"], served["sizes"]
    assert not ruleset.host_fallback
    tiers = collections.Counter(ruleset.atom_tier.values())
    # one automaton a block, each its own; five x-version constants
    assert tiers == {"dfa-pack": sizes["rules"],
                     "id-eq": sizes["services"], "tensor": 5}
    assert ruleset.geometry["n_dfa_groups"] == 2


def test_each_bank_takes_the_tier_its_size_and_guard_give(served):
    banks = served["ruleset"].geometry["dfa_banks"]
    got = {("cookie" if "cookie" in b["subject"] else b["subject"]):
           (b["tier"], b["candidates"]) for b in banks}
    assert got == served["tiers"]
    assert sum(b["automata"] for b in banks) == served["sizes"]["rules"]


def test_plan_build_sets_the_bank_gauges_and_times_the_build(served):
    banks = served["ruleset"].geometry["dfa_banks"]
    for b in banks:
        assert b["bytes"] > 0
        assert monitor.DFA_BANK_BYTES.value(subject=b["subject"]) \
            == b["bytes"]
        assert monitor.DFA_BANK_AUTOMATA.value(
            subject=b["subject"], tier=b["tier"]) == b["automata"]
        assert monitor.DFA_CANDIDATES_MAX.value(subject=b["subject"]) \
            == b["candidates"]
    # a series of a bank this plan does not have reads 0
    for gauge, field in ((monitor.DFA_BANK_BYTES, "bytes"),
                         (monitor.DFA_CANDIDATES_MAX, "candidates")):
        assert sum(gauge.value(**labels) for labels in gauge.label_sets()) \
            == sum(b[field] for b in banks)
    # regex -> DFA, then the pack: two intervals of one span
    assert served["spans"]["build.dfa"]["count"] == 2
    assert served["spans"]["build.dfa"]["sum_ms"] > 0


def test_served_entry_and_wire_are_the_reference_is_the_host_oracle(
        served):
    assert served["expected"] == served["oracle"]
    assert served["got"] == served["expected"][:ROWS]
    # the row at the cap: see below
    assert served["wire"][:WIRE] == served["expected"][:WIRE]
    assert len(set(served["wire"][:WIRE])) > 1
    hist = collections.Counter(served["expected"][:ROWS])
    assert set(hist) == {0, 5, 7}
    assert 0.15 < 1 - hist[0] / ROWS < 0.30


@pytest.mark.parametrize("kind, share, blocks", [
    ("one", 0.5, 1), ("two", 0.1, 2), ("none", 0.4, 0)])
def test_each_traffic_class_matches_the_blocks_it_says(served, kind,
                                                       share, blocks):
    assert served["config"].CLASSES == ("one", "two", "none")
    rows = [i for i, d in enumerate(served["requests"][:ROWS])
            if len(_matching(served, d)) == blocks]
    assert abs(len(rows) / ROWS - share) < 0.07
    specs = served["specs"]
    for i in rows:
        request = served["requests"][i]
        want = 0
        for r in _matching(served, request):   # the lower index decides
            if specs[r]["deny"]:
                want = 7
            elif specs[r]["whitelist"] and request["source.namespace"] \
                    not in served["config"].WHITELIST:
                want = 5
            if want:
                break
        assert served["got"][i] == want, request
    if kind == "two":   # a request-line block and a cookie block
        for i in rows:
            a, b = _matching(served, served["requests"][i])
            families = {served["config"].family_of(served["sizes"], r)
                        for r in (a, b)}
            assert 2 in families and len(families) == 2


def test_rows_without_a_cookie_and_the_row_at_the_cap(served):
    bare = [i for i, d in enumerate(served["requests"][:ROWS])
            if "cookie" not in d["request.headers"]]
    assert abs(len(bare) / ROWS - 0.25) < 0.07
    assert {served["got"][i] for i in bare} >= {0, 7}
    capped = served["requests"][ROWS]
    assert len(capped["request.path"]) >= 128
    assert _matching(served, capped)
    assert served["expected"][ROWS] == served["oracle"][ROWS] == 7


def test_the_wire_answers_the_row_at_the_cap_as_the_snapshot_does(served):
    assert served["wire"][WIRE] == served["expected"][ROWS] == 7


@pytest.mark.parametrize("scale", ["smoke", "full"])
def test_requests_carry_the_named_header_shares(scale):
    """ISSUE 33: a cookie on three requests in four, x-version on
    half, over ALL requests (the classes that need a header count)."""
    sizes, config = _load("routematch10k")
    if scale == "full":
        sizes = json.loads((CONFIGS / "routematch10k.json").read_text())
    requests = config.make_requests(sizes, 16384, SEED)
    for header, share in (("cookie", 0.75), ("x-version", 0.5)):
        have = sum(header in d["request.headers"] for d in requests)
        assert abs(have / len(requests) - share) < 0.02, header


def test_strings_are_at_real_widths_and_none_reaches_the_cap(served):
    requests = served["requests"][:ROWS]
    paths = [d["request.path"] for d in requests]
    cookies = [d["request.headers"]["cookie"] for d in requests
               if "cookie" in d["request.headers"]]
    assert len(set(paths)) == ROWS
    assert 24 <= min(map(len, paths)) and max(map(len, paths)) <= 72
    assert 48 <= min(map(len, cookies)) and max(map(len, cookies)) <= 112
    versions = sum("x-version" in d["request.headers"] for d in requests)
    assert abs(versions / ROWS - 0.5) < 0.07


def test_mixer10k_regex_group_keeps_its_dense_onehot():
    """The tier chosen for a route table must not reach the north
    star's four-pattern bank."""
    sizes, config = _load("mixer10k")
    srv = _server(sizes, config)
    try:
        bank, = srv.controller.dispatcher.fused.engine.ruleset \
            .geometry["dfa_banks"]
    finally:
        srv.close()
    assert bank == {"subject": "$request.path", "tier": "onehot",
                    "automata": 2, "bytes": bank["bytes"],
                    "candidates": 2}
    assert bank["bytes"] < 1 << 20
