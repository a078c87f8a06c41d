"""Fused serving path — device engine wired into the check path.

Proves the VERDICT r1 requirement: the fused PolicyEngine serves real
config-driven checks, and its verdicts agree with the generic
host-adapter dispatcher field-by-field across denier / fused list /
host-only list / host-fallback-predicate / namespace-scoped rules.
Anchor: mixer/pkg/server/server.go:92 (the served runtime is the
benchmarked runtime)."""
import pytest

from istio_tpu.attribute.bag import bag_from_mapping
from istio_tpu.models.policy_engine import (NOT_FOUND, OK,
                                            PERMISSION_DENIED)
from istio_tpu.runtime import MemStore, RuntimeServer, ServerArgs
from istio_tpu.runtime.fused import build_fused_plan


def _store() -> MemStore:
    s = MemStore()
    # fused: static case-sensitive whitelist over a bare attribute
    s.set(("handler", "istio-system", "nswhitelist"), {
        "adapter": "list",
        "params": {"overrides": ["default", "prod"], "blacklist": False,
                   "caching_ttl_s": 30.0}})
    # fused: blacklist over a map-derived slot
    s.set(("handler", "istio-system", "uablacklist"), {
        "adapter": "list",
        "params": {"overrides": ["badbot"], "blacklist": True}})
    # host: fallback expression (`|` default) keeps list.go semantics
    s.set(("handler", "istio-system", "verwhitelist"), {
        "adapter": "list",
        "params": {"overrides": ["v1", "v2"], "blacklist": False}})
    # fused since r4: static REGEX entries lower to a device DFA bank
    s.set(("handler", "istio-system", "rxlist"), {
        "adapter": "list",
        "params": {"overrides": ["^/api/"], "entry_type": "REGEX",
                   "blacklist": True}})
    # fused since r4: CIDR entries lower to device prefix compares
    s.set(("handler", "istio-system", "ipblock"), {
        "adapter": "list",
        "params": {"overrides": ["10.0.0.0/8", "2001:db8::/32"],
                   "entry_type": "IP_ADDRESSES", "blacklist": True}})
    # host: case-insensitive matching has no device lowering
    s.set(("handler", "istio-system", "cilist"), {
        "adapter": "list",
        "params": {"overrides": ["Mozilla"],
                   "entry_type": "CASE_INSENSITIVE_STRINGS",
                   "blacklist": True}})
    s.set(("handler", "istio-system", "denyall"), {
        "adapter": "denier",
        "params": {"status_code": PERMISSION_DENIED,
                   "status_message": "admin is off limits",
                   "valid_duration_s": 3.0, "valid_use_count": 100}})
    s.set(("instance", "istio-system", "srcns"), {
        "template": "listentry", "params": {"value": "source.namespace"}})
    s.set(("instance", "istio-system", "ua"), {
        "template": "listentry",
        "params": {"value": 'request.headers["user-agent"]'}})
    s.set(("instance", "istio-system", "appversion"), {
        "template": "listentry",
        "params": {"value": 'source.labels["version"] | "none"'}})
    s.set(("instance", "istio-system", "path"), {
        "template": "listentry", "params": {"value": "request.path"}})
    s.set(("instance", "istio-system", "srcip"), {
        "template": "listentry", "params": {"value": "source.ip"}})
    s.set(("instance", "istio-system", "nothing"), {
        "template": "checknothing", "params": {}})
    # global rules (config namespace = mesh-wide)
    s.set(("rule", "istio-system", "r0-denyadmin"), {
        "match": 'request.path.startsWith("/admin")',
        "actions": [{"handler": "denyall", "instances": ["nothing"]}]})
    s.set(("rule", "istio-system", "r1-nscheck"), {
        "match": 'destination.service == "ratings.default.svc.cluster.local"',
        "actions": [{"handler": "nswhitelist", "instances": ["srcns"]}]})
    s.set(("rule", "istio-system", "r2-uacheck"), {
        "match": "connection.mtls",
        "actions": [{"handler": "uablacklist", "instances": ["ua"]}]})
    s.set(("rule", "istio-system", "r3-version"), {
        "match": 'request.method == "POST"',
        "actions": [{"handler": "verwhitelist",
                     "instances": ["appversion"]}]})
    s.set(("rule", "istio-system", "r4-rx"), {
        "match": 'request.scheme == "http"',
        "actions": [{"handler": "rxlist", "instances": ["path"]}]})
    # host-fallback predicate (dynamic map key) with a fused-type action
    s.set(("rule", "istio-system", "r5-dynkey"), {
        "match": 'request.headers[request.method] == "x"',
        "actions": [{"handler": "denyall", "instances": ["nothing"]}]})
    # namespace-scoped rule: only for destination.service *.prod.*
    # (handler/instance refs are cross-namespace → fully qualified)
    s.set(("rule", "prod", "r6-prodonly"), {
        "match": 'request.size > 100',
        "actions": [{"handler": "denyall.istio-system",
                     "instances": ["nothing.istio-system"]}]})
    # same rule mixes a fused action (denier, first) and a host action
    # (fallback-expr whitelist, second): device status must win the
    # status tie, matching the generic path's config action order
    s.set(("rule", "istio-system", "r7-mixed"), {
        "match": 'request.method == "DELETE"',
        "actions": [{"handler": "denyall", "instances": ["nothing"]},
                    {"handler": "verwhitelist",
                     "instances": ["appversion"]}]})
    s.set(("rule", "istio-system", "r8-ip"), {
        "match": 'request.scheme == "https"',
        "actions": [{"handler": "ipblock", "instances": ["srcip"]}]})
    s.set(("rule", "istio-system", "r9-ci"), {
        "match": 'request.useragent == "x"',
        "actions": [{"handler": "cilist", "instances": ["ua"]}]})
    return s


def _bags():
    cases = [
        {"request.path": "/admin/keys"},                       # denier
        {"request.path": "/ratings/1"},                        # clean
        {"destination.service": "ratings.default.svc.cluster.local",
         "source.namespace": "default"},                       # wl pass
        {"destination.service": "ratings.default.svc.cluster.local",
         "source.namespace": "evil"},                          # wl miss
        {"connection.mtls": True,
         "request.headers": {"user-agent": "badbot"}},         # bl hit
        {"connection.mtls": True,
         "request.headers": {"user-agent": "chrome"}},         # bl miss
        {"request.method": "POST",
         "source.labels": {"version": "v2"}},                  # host wl pass
        {"request.method": "POST",
         "source.labels": {"version": "v9"}},                  # host wl miss
        {"request.method": "POST"},                            # fallback val
        {"request.scheme": "http", "request.path": "/api/x"},  # regex hit
        {"request.scheme": "http", "request.path": "/web/x"},  # regex miss
        {"request.method": "GET",
         "request.headers": {"GET": "x"}},                     # dyn-key deny
        {"request.method": "GET",
         "request.headers": {"GET": "y"}},                     # dyn-key pass
        {"destination.service": "api.prod.svc.cluster.local",
         "request.size": 500},                                 # ns rule hit
        {"destination.service": "api.other.svc.cluster.local",
         "request.size": 500},                                 # ns rule inert
        # combined: denier (rule 0) outranks whitelist miss (rule 1) —
        # lowest-rule-index-wins on both paths
        {"request.path": "/admin/x",
         "destination.service": "ratings.default.svc.cluster.local",
         "source.namespace": "evil"},
        # same-rule tie: fused denier action listed before a host
        # whitelist miss — denier's status wins on both paths
        {"request.method": "DELETE",
         "source.labels": {"version": "v9"}},
        # CIDR list (device prefix compare) — v4-mapped 16-byte hit,
        # 4-byte raw hit, v6 net hit, v4 miss
        {"request.scheme": "https",
         "source.ip": b"\x00" * 10 + b"\xff\xff" + bytes([10, 1, 2, 3])},
        {"request.scheme": "https", "source.ip": bytes([10, 0, 0, 1])},
        {"request.scheme": "https",
         "source.ip": bytes.fromhex("20010db8") + b"\x00" * 12},
        {"request.scheme": "https",
         "source.ip": b"\x00" * 10 + b"\xff\xff" + bytes([11, 1, 2, 3])},
        # case-insensitive list stays host-side on both paths
        {"request.useragent": "x",
         "request.headers": {"user-agent": "mozilla"}},
        # REGEX truncation contract: a $-free prefix hit on a truncated
        # value is definitive (deny on both paths); a truncated miss is
        # undecidable → device errs the rule and fails open, matching
        # the host's allow here because the full value has no match
        # either
        {"request.scheme": "http",
         "request.path": "/api/" + "x" * 200},
        {"request.scheme": "http",
         "request.path": "/web/" + "x" * 200},
    ]
    return [bag_from_mapping(c) for c in cases]


@pytest.fixture(scope="module")
def servers():
    fused = RuntimeServer(_store(), ServerArgs(batch_window_s=0.001,
                                               fused=True))
    generic = RuntimeServer(_store(), ServerArgs(batch_window_s=0.001,
                                                 fused=False))
    yield fused, generic
    fused.close()
    generic.close()


def test_plan_extraction(servers):
    fused, _ = servers
    plan = fused.controller.dispatcher.fused
    assert plan is not None
    snap = fused.controller.dispatcher.snapshot
    # r0 + r6 + r7 fuse (ordered comparisons lower via byte order
    # keys since r3); r5 (dynamic map key) stays host-fallback
    assert plan.fused_deny == 3
    # srcns + ua + rx path + cidr srcip; appversion (fallback expr)
    # and the case-insensitive list stay host
    assert plan.fused_lists == 4
    host_rules = {snap.rules[i].name for i in plan.host_actions}
    assert "r3-version" in host_rules    # `|` fallback expr
    assert "r4-rx" not in host_rules     # REGEX fuses since r4
    assert "r8-ip" not in host_rules     # CIDR fuses since r4
    assert "r9-ci" in host_rules         # case-insensitive: host
    assert "r5-dynkey" in host_rules     # predicate host fallback
    assert "r6-prodonly" not in host_rules   # GTR now on device
    assert "CASE_INSENSITIVE_STRINGS" in plan.unfused_list_kinds
    assert "STRINGS:value-not-lowerable" in plan.unfused_list_kinds


def test_fused_matches_generic(servers):
    fused, generic = servers
    bags = _bags()
    rf = fused.check_many(bags)
    rg = generic.check_many(bags)
    for i, (a, b) in enumerate(zip(rf, rg)):
        assert a.status_code == b.status_code, \
            f"case {i}: fused={a.status_code} generic={b.status_code}"
        assert a.valid_duration_s == pytest.approx(b.valid_duration_s), i
        assert a.valid_use_count == b.valid_use_count, i
        assert a.referenced == b.referenced, i


def test_fused_statuses(servers):
    fused, _ = servers
    r = fused.check_many(_bags())
    assert r[0].status_code == PERMISSION_DENIED
    assert r[0].status_message == "admin is off limits"
    assert r[0].valid_duration_s == pytest.approx(3.0)
    assert r[0].valid_use_count == 100
    assert r[1].status_code == OK
    assert r[2].status_code == OK
    assert r[3].status_code == NOT_FOUND
    assert r[4].status_code == PERMISSION_DENIED   # blacklist hit
    assert r[5].status_code == OK
    assert r[11].status_code == PERMISSION_DENIED  # host-fallback deny
    assert r[13].status_code == PERMISSION_DENIED  # prod-ns rule
    assert r[14].status_code == OK                 # other ns: inert
    assert r[15].status_code == PERMISSION_DENIED  # lowest rule wins
    assert r[15].status_message == "admin is off limits"


def test_fused_list_edge_values_match_generic():
    """Device-lowered REGEX/CIDR lists on edge inputs — absent values,
    malformed IP byte lengths, unparseable addresses — must agree with
    the host adapter."""
    def store() -> MemStore:
        s = MemStore()
        s.set(("handler", "istio-system", "rx"), {
            "adapter": "list",
            "params": {"overrides": ["^/blocked/"],
                       "entry_type": "REGEX", "blacklist": True}})
        s.set(("handler", "istio-system", "cidr"), {
            "adapter": "list",
            "params": {"overrides": ["10.0.0.0/8"],
                       "entry_type": "IP_ADDRESSES",
                       "blacklist": False}})
        s.set(("instance", "istio-system", "path"), {
            "template": "listentry", "params": {"value": "request.path"}})
        s.set(("instance", "istio-system", "ip"), {
            "template": "listentry", "params": {"value": "source.ip"}})
        s.set(("rule", "istio-system", "r0"), {
            "match": 'request.scheme == "http"',
            "actions": [{"handler": "rx", "instances": ["path"]}]})
        s.set(("rule", "istio-system", "r1"), {
            "match": 'request.scheme == "https"',
            "actions": [{"handler": "cidr", "instances": ["ip"]}]})
        return s

    bags = [bag_from_mapping(c) for c in (
        {"request.scheme": "http"},                       # path absent
        {"request.scheme": "http", "request.path": ""},   # empty value
        {"request.scheme": "https"},                      # ip absent
        {"request.scheme": "https",
         "source.ip": b"\x01\x02\x03"},                   # 3-byte junk
        {"request.scheme": "https",
         "source.ip": bytes([10, 0, 0, 1])},              # in CIDR
    )]
    fused = RuntimeServer(store(), ServerArgs(fused=True))
    generic = RuntimeServer(store(), ServerArgs(fused=False))
    try:
        rf = fused.check_many(bags)
        rg = generic.check_many(bags)
        for i, (a, b) in enumerate(zip(rf, rg)):
            assert a.status_code == b.status_code, \
                (i, a.status_code, b.status_code)
    finally:
        fused.close()
        generic.close()


def test_ip_typed_values_keep_host_semantics():
    """Two configs that LOOK fusable but must stay host-side: a STRINGS
    list over an IP_ADDRESS-typed value (host normalizes bytes to a
    textual IP before matching — the id scan never would), and an
    IP_ADDRESSES list over a map-derived TEXT value (the device
    compares raw bytes against binary CIDR prefixes — text would flip
    verdicts). Both were reproduced as fused-vs-generic divergences in
    the r4 review."""
    def store() -> MemStore:
        s = MemStore()
        s.set(("handler", "istio-system", "strlist"), {
            "adapter": "list",
            "params": {"overrides": ["10.0.0.1"], "blacklist": False}})
        s.set(("handler", "istio-system", "iptext"), {
            "adapter": "list",
            "params": {"overrides": ["10.0.0.0/8"],
                       "entry_type": "IP_ADDRESSES",
                       "blacklist": False}})
        s.set(("instance", "istio-system", "ipinst"), {
            "template": "listentry", "params": {"value": "source.ip"}})
        s.set(("instance", "istio-system", "hdrip"), {
            "template": "listentry",
            "params": {"value": 'request.headers["x-ip"]'}})
        s.set(("rule", "istio-system", "r0"), {
            "match": 'request.scheme == "http"',
            "actions": [{"handler": "strlist", "instances": ["ipinst"]}]})
        s.set(("rule", "istio-system", "r1"), {
            "match": 'request.scheme == "https"',
            "actions": [{"handler": "iptext", "instances": ["hdrip"]}]})
        return s

    fused = RuntimeServer(store(), ServerArgs(fused=True))
    generic = RuntimeServer(store(), ServerArgs(fused=False))
    try:
        plan = fused.controller.dispatcher.fused
        assert plan.fused_lists == 0
        assert "STRINGS:value-not-lowerable" in plan.unfused_list_kinds
        assert "IP_ADDRESSES:value-not-lowerable" in \
            plan.unfused_list_kinds
        bags = [bag_from_mapping(c) for c in (
            {"request.scheme": "http",
             "source.ip": bytes([10, 0, 0, 1])},      # listed (as text)
            {"request.scheme": "http",
             "source.ip": bytes([10, 9, 9, 9])},      # not listed
            {"request.scheme": "https",
             "request.headers": {"x-ip": "10.1.2.3"}},   # in CIDR
            {"request.scheme": "https",
             "request.headers": {"x-ip": "11.1.2.3"}},   # outside
        )]
        rf = fused.check_many(bags)
        rg = generic.check_many(bags)
        assert [r.status_code for r in rg] == [OK, NOT_FOUND,
                                               OK, NOT_FOUND]
        for i, (a, g) in enumerate(zip(rf, rg)):
            assert a.status_code == g.status_code, i
    finally:
        fused.close()
        generic.close()


def test_report_parity_fused_vs_generic():
    """dispatcher.report rides the fused packed step (one bitpacked
    overlay pull) when a plan exists; adapter effects must equal the
    generic full-plane path — including namespace-scoped report rules
    and predicate-gated ones."""
    def store() -> MemStore:
        s = MemStore()
        s.set(("handler", "istio-system", "prom"), {
            "adapter": "prometheus",
            "params": {"metrics": [{"name": "hits.istio-system",
                                    "kind": "COUNTER",
                                    "label_names": ["dest"]}]}})
        s.set(("instance", "istio-system", "hits"), {
            "template": "metric",
            "params": {"value": "1",
                       "dimensions": {"dest": "destination.service"}}})
        s.set(("rule", "istio-system", "tally"), {
            "match": 'request.method == "GET"',
            "actions": [{"handler": "prom", "instances": ["hits"]}]})
        # namespace-scoped report rule: only prod-destined requests
        s.set(("rule", "prod", "tally-prod"), {
            "match": "",
            "actions": [{"handler": "prom.istio-system",
                         "instances": ["hits.istio-system"]}]})
        return s

    bags = [bag_from_mapping(c) for c in (
        {"request.method": "GET",
         "destination.service": "a.default.svc"},
        {"request.method": "POST",
         "destination.service": "a.default.svc"},   # predicate miss
        {"request.method": "GET",
         "destination.service": "b.default.svc"},
        {"request.method": "GET",
         "destination.service": "b.default.svc"},
        # prod namespace: BOTH the global rule (GET) and the prod rule
        # fire → +2; POST hits only the prod rule → +1
        {"request.method": "GET",
         "destination.service": "c.prod.svc"},
        {"request.method": "POST",
         "destination.service": "c.prod.svc"},
    )]
    want = {"a.default.svc": 1.0, "b.default.svc": 2.0,
            "c.prod.svc": 3.0}
    samples = {}
    for fused in (True, False):
        # tiny buckets: the 6-bag report must CHUNK (4+2) and pad on
        # the fused path — oversize report batches never reach the
        # device at arbitrary shapes
        srv = RuntimeServer(store(), ServerArgs(fused=fused,
                                                max_batch=4,
                                                buckets=(4,)))
        try:
            d = srv.controller.dispatcher
            assert (d.fused is not None) == fused
            d.report(bags)
            h = d.handlers["prom.istio-system"]
            samples[fused] = {
                dest: h.registry.get_sample_value(
                    "istio_tpu_hits_istio_system_total",
                    {"dest": dest})
                for dest in want}
        finally:
            srv.close()
    assert samples[True] == samples[False] == want


def test_wire_fast_path_zero_decode():
    """gRPC → C++ tensorize → device step → response, with NO python
    wire decode when every matched rule is fully fused (the mixerclient
    contract, SURVEY §2.9(a); VERDICT r1 item 4)."""
    import grpc  # noqa: F401 (skip gracefully if grpcio missing)
    from istio_tpu.api.grpc_server import MixerGrpcServer
    from istio_tpu.api.client import MixerClient
    from istio_tpu.api.wire import LazyWireBag
    from istio_tpu.runtime import MemStore

    s = MemStore()
    s.set(("handler", "istio-system", "denyall"), {
        "adapter": "denier", "params": {"status_code": PERMISSION_DENIED}})
    s.set(("instance", "istio-system", "nothing"), {
        "template": "checknothing", "params": {}})
    s.set(("rule", "istio-system", "deny-admin"), {
        "match": 'request.path.startsWith("/admin")',
        "actions": [{"handler": "denyall", "instances": ["nothing"]}]})
    # no drain thread: a drain renders the denied request's exemplar
    # (a decode, off the serving path by design) whenever it happens
    # to fall inside the spy
    srv = RuntimeServer(s, ServerArgs(batch_window_s=0.001,
                                      rulestats_drain_s=0.0))
    plan = srv.controller.dispatcher.fused
    if plan.native is None:
        srv.close()
        pytest.skip("native toolchain unavailable")

    parses = []
    orig = LazyWireBag._decode

    def spy(self):
        if self._values is None:
            parses.append(1)
        return orig(self)

    LazyWireBag._decode = spy
    try:
        g = MixerGrpcServer(srv)
        port = g.start()
        c = MixerClient(f"127.0.0.1:{port}")
        deny = c.check({"request.path": "/admin/x",
                        "destination.service": "a.default.svc"})
        ok = c.check({"request.path": "/ok",
                      "request.headers": {"x": "y"}})
        g.stop()
    finally:
        LazyWireBag._decode = orig
        srv.close()
    assert deny.precondition.status.code == PERMISSION_DENIED
    assert ok.precondition.status.code == OK
    # referenced attributes still populated (from device planes)
    assert len(deny.precondition.referenced_attributes.attribute_matches)
    assert parses == []


def test_short_global_dict_falls_back_to_python_path(servers):
    """A client with a shortened global-dictionary prefix can't ride
    the C++ decoder; the server must still answer correctly via the
    python wire path (grpcServer.go global dict plumbing)."""
    import grpc
    from istio_tpu.api import mixer_pb2 as pb
    from istio_tpu.api.grpc_server import MixerGrpcServer
    from istio_tpu.api.wire import bag_to_compressed

    fused, _ = servers
    g = MixerGrpcServer(fused)
    port = g.start()
    try:
        chan = grpc.insecure_channel(f"127.0.0.1:{port}")
        call = chan.unary_unary(
            "/istio.mixer.v1.Mixer/Check",
            request_serializer=pb.CheckRequest.SerializeToString,
            response_deserializer=pb.CheckResponse.FromString)
        req = pb.CheckRequest(global_word_count=10)
        bag_to_compressed({"request.path": "/admin/keys"}, 10,
                          msg=req.attributes)
        resp = call(req)
        assert resp.precondition.status.code == PERMISSION_DENIED
        chan.close()
    finally:
        g.stop()


def test_batch_check_short_global_dict(servers):
    """BatchCheck with a shortened global-dictionary prefix: every bag
    decodes through the python wire path and per-item verdicts match
    the unary short-dict behavior."""
    import grpc
    from istio_tpu.api.grpc_server import MixerGrpcServer
    from istio_tpu.api import mixer_pb2 as pb
    from istio_tpu.api.wire import (bag_to_compressed,
                                    decode_batch_check_response,
                                    encode_batch_check_request)

    fused, _ = servers
    g = MixerGrpcServer(fused)
    port = g.start()
    try:
        blobs = []
        for path in ("/admin/keys", "/ratings/1"):
            msg = pb.CompressedAttributes()
            bag_to_compressed({"request.path": path}, 10, msg=msg)
            blobs.append(msg.SerializeToString())
        chan = grpc.insecure_channel(f"127.0.0.1:{port}")
        call = chan.unary_unary(
            "/istio.mixer.v1.Mixer/BatchCheck",
            request_serializer=lambda b: b,
            response_deserializer=lambda b: b)
        raw = call(encode_batch_check_request(blobs, 10))
        codes = [pb.CheckResponse.FromString(b).precondition.status.code
                 for b in decode_batch_check_response(raw)]
        assert codes == [PERMISSION_DENIED, OK]
        chan.close()
    finally:
        g.stop()


def test_snapshot_swap_under_load():
    """A config swap must never surface compile time in-band: the old
    snapshot serves while the new one's jit buckets pre-warm (SURVEY
    hard-part #5; resolver refcount swap, resolver.go:240-247)."""
    import threading
    import time as _time

    from istio_tpu.testing import workloads

    store = workloads.make_store(300)
    srv = RuntimeServer(store, ServerArgs(
        batch_window_s=0.001, max_batch=64, buckets=(16, 64),
        default_manifest=workloads.MESH_MANIFEST))
    try:
        bags = workloads.make_bags(64)
        srv.check_many(bags[:16])        # warm initial snapshot buckets
        srv.check_many(bags[:64])

        latencies: list[float] = []
        stop = threading.Event()

        def stream():
            i = 0
            while not stop.is_set():
                t0 = _time.perf_counter()
                srv.check(bags[i % len(bags)])
                latencies.append(_time.perf_counter() - t0)
                i += 1

        threads = [threading.Thread(target=stream, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        _time.sleep(0.3)
        baseline_n = len(latencies)
        # config change → debounce → rebuild + prewarm → atomic swap.
        # The pre-swap warm covers ONLY the (bucket, byte-tier) shapes
        # live traffic is serving (the old plan's observed set), with
        # a serving-latency backoff between compiles; the remaining
        # shapes warm post-swap in the background with the host-oracle
        # bridge covering any batch that races onto them — so the swap
        # completes in a couple of compiles' time by construction,
        # even on a loaded single core.
        store.set(("rule", "istio-system", "swap-deny"), {
            "match": 'request.path.startsWith("/swapped")',
            "actions": [{"handler": "denyall.istio-system",
                         "instances": ["nothing.istio-system"]}]})
        deadline = _time.time() + 30
        while _time.time() < deadline:
            r = srv.check(bag_from_mapping(
                {"request.path": "/swapped/x"}))
            if r.status_code == PERMISSION_DENIED:
                break
            _time.sleep(0.05)
        else:
            raise AssertionError("swap never took effect")
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert len(latencies) > baseline_n   # streaming continued
        worst = max(latencies)
        # Without prewarm a post-swap request pays the full in-band
        # trace+compile (the whole ~10s rebuild). With prewarm the
        # worst case is GIL starvation while the controller thread
        # traces the new snapshot's jaxprs (pure-Python, seconds at
        # 300 rules) — real but bounded, and well under the in-band
        # compile cost this test exists to catch.
        assert worst < 4.0, f"request saw {worst:.2f}s during swap"
        fast = sorted(latencies)[int(len(latencies) * 0.95)]
        assert fast < 0.5, f"p95 {fast:.2f}s during swap"
    finally:
        srv.close()


def test_swap_warm_bridge_serves_oracle_without_device():
    """While a warm is pending, a batch at a not-yet-compiled shape
    must serve through the CPU oracle (same verdicts, zero device
    packer calls — no in-band XLA trace); once the warm ends the
    device path resumes. The mechanism behind swap-under-load's ≤30s
    completion: un-warmed shapes never block or compile in-band."""
    from istio_tpu.runtime.batcher import pad_to_bucket

    srv = RuntimeServer(_store(), ServerArgs(
        batch_window_s=0.001, max_batch=8, buckets=(8,),
        initial_prewarm=False))
    try:
        d = srv.controller.dispatcher
        plan = d.fused
        bags = pad_to_bucket(
            [bag_from_mapping({"request.path": "/admin/keys"}),
             bag_from_mapping({"request.path": "/ratings/1"})], (8,))
        baseline = d.check(bags)        # compiles + registers shape
        calls: list = []
        orig = plan.packed_check
        plan.packed_check = \
            lambda *a, **k: (calls.append(1), orig(*a, **k))[1]
        plan._warmed_shapes.clear()     # shape "not yet compiled"
        plan.begin_warm()
        try:
            bridged = d.check(bags)
            assert not calls, "bridged batch still hit the device"
            assert [r.status_code for r in bridged] == \
                [r.status_code for r in baseline]
        finally:
            plan.end_warm()
        resumed = d.check(bags)
        assert calls, "device path did not resume after the warm"
        assert [r.status_code for r in resumed] == \
            [r.status_code for r in baseline]
    finally:
        srv.close()


def test_map_served_shapes_prioritizes_live_traffic():
    """The pre-swap warm set: live-served (bucket, width) pairs map
    onto the candidate plan's tiers (width → smallest holding tier);
    no observed traffic falls back to the full shape product."""
    srv = RuntimeServer(_store(), ServerArgs(
        batch_window_s=0.001, max_batch=32, buckets=(8, 32),
        initial_prewarm=False))
    try:
        plan = srv.controller.dispatcher.fused
        pairs = plan.all_warm_shapes((8, 32))
        assert plan.map_served_shapes((8, 32), set()) == pairs
        small_tier = pairs[0][1]
        sel = plan.map_served_shapes((8, 32), {(8, small_tier)})
        assert sel == [(8, small_tier)]
        # a width no tier holds maps to the largest; foreign buckets
        # are dropped (the wide program's one pair, the last, aside)
        assert pairs[-1] == (32, plan.wide_width)
        big = max(t for _, t in pairs[:-1])
        sel = plan.map_served_shapes((8, 32), {(8, big + 1),
                                               (999, small_tier)})
        assert sel == [(8, big)]
        # the wide program's shape maps onto itself, whatever bucket
        # the old plan launched it at
        assert plan.map_served_shapes(
            (8, 32), {(8, plan.wide_width)}) == [pairs[-1]]
    finally:
        srv.close()


def test_prewarm_treedef_matches_serving():
    """The prewarm dummy batch and every real tensorizer's batches
    must flatten to the SAME pytree treedef — a mismatch compiles a
    jit cache entry serving never hits, silently re-introducing
    in-band compile on the first real request (the exact failure the
    prewarm exists to prevent)."""
    import jax
    import numpy as np
    from istio_tpu.compiler.layout import AttributeBatch, Tensorizer
    from istio_tpu.testing import workloads

    eng = workloads.make_engine(n_rules=8, jit=False)
    lay = eng.ruleset.layout
    b = 4
    dummy = AttributeBatch(
        ids=np.zeros((b, lay.n_columns), np.int32),
        present=np.zeros((b, lay.n_columns), bool),
        map_present=np.zeros((b, max(lay.n_maps, 1)), bool),
        str_bytes=np.zeros((b, max(lay.n_byte_slots, 1),
                            lay.max_str_len), np.uint8),
        str_lens=np.zeros((b, max(lay.n_byte_slots, 1)), np.int32),
        hash_ids=np.zeros((b, lay.n_columns), np.int32))
    real = eng.tensorizer.tensorize(workloads.make_bags(b))
    plain = Tensorizer(lay, eng.ruleset.interner).tensorize(
        workloads.make_bags(b))
    td = lambda x: jax.tree_util.tree_structure(x)
    assert td(dummy) == td(real) == td(plain)


def test_fused_config_swap(servers):
    """A store change rebuilds the plan (new engine) atomically."""
    fused, _ = servers
    store = fused.controller.store
    plan_before = fused.controller.dispatcher.fused
    store.set(("rule", "istio-system", "r9-extra"), {
        "match": 'request.path.startsWith("/secret")',
        "actions": [{"handler": "denyall", "instances": ["nothing"]}]})
    fused.controller.rebuild()
    plan_after = fused.controller.dispatcher.fused
    assert plan_after is not plan_before
    r = fused.check(bag_from_mapping({"request.path": "/secret/x"}))
    assert r.status_code == PERMISSION_DENIED
    store.delete(("rule", "istio-system", "r9-extra"))
    fused.controller.rebuild()


# ---------------------------------------------------------------------------
# one device program a Check batch (FusedPlan._base_step): the engine
# step, the rule-telemetry delta + fold and the packer in one jit, held
# to the same closures launched apart
# ---------------------------------------------------------------------------

_LONG = "/api/v1/" + "x" * 60     # past the 32-byte tier


def _one_program_world(kind):
    """(snapshot, request dicts): a rules + lists deployment and an
    RBAC one at the benchmark's smoke sizes, with traffic that hits,
    is denied and errors."""
    from istio_tpu.runtime.config import SnapshotBuilder
    from istio_tpu.testing import workloads

    if kind == "rbac":
        store = workloads.make_rbac_store(50)
        dicts = workloads.make_rbac_request_dicts(40)
    else:
        store = workloads.make_store(200)
        # random mesh traffic; rows aimed at rule i (every third
        # denies); rows that lack what a rule their namespace sees
        # reads (the service, rule5's cookie), which errors
        dicts = workloads.make_request_dicts(8, seed=5) + [{
            "destination.service": f"svc{i}.ns{i % 23}.svc.cluster.local",
            "source.namespace": f"ns{(i * 5) % 25}",
            "request.method": "GET",
            "request.path": "/api/v0/products/1",
            "request.host": f"x.ns{i % 23}.cluster.local",
            "connection.mtls": True,
            "request.headers": {"cookie": "session=0"},
        } for i in range(30)] + [
            {"source.namespace": "ns1"},
            {"source.namespace": "ns5",
             "destination.service": "svc5.ns5.svc.cluster.local"}]
    snap = SnapshotBuilder(
        default_manifest=workloads.MESH_MANIFEST).build(store)
    return snap, dicts


@pytest.fixture(scope="module", params=["rules+lists", "rbac"])
def one_program(request):
    """(plan serving through packed_check, a second plan of the same
    snapshot to launch the closures apart on, request dicts)."""
    snap, dicts = _one_program_world(request.param)
    plan, apart = build_fused_plan(snap), build_fused_plan(snap)
    assert plan.mesh is None and plan.telemetry is not None
    return plan, apart, dicts


def _padded_batch(plan, dicts, tier, bucket=64):
    """The dicts tensorized and padded to `bucket` rows, routed to
    byte tier `tier` (a long path on one row reaches the wide one)."""
    import numpy as np

    from istio_tpu.runtime.batcher import pad_to_bucket

    dicts = [dict(d) for d in dicts]
    if tier > min(plan.str_tiers):
        dicts[1]["request.path"] = _LONG
    bags = pad_to_bucket([bag_from_mapping(d) for d in dicts], (bucket,))
    batch = plan.engine.tensorizer.tensorize(bags)
    rs = plan.engine.ruleset
    ns = np.asarray([rs.namespace_id(str(d.get("source.namespace", "")))
                     for d in dicts] + [0] * (bucket - len(dicts)),
                    np.int32)
    ns[2] = -1                     # a namespace the snapshot never saw
    assert int(plan.narrow_batch(batch).str_bytes.shape[2]) == tier
    return batch, ns, len(dicts)


@pytest.mark.parametrize("tier_at", [0, -1], ids=["narrow", "wide"])
def test_one_program_equals_the_closures_launched_apart(one_program,
                                                        tier_at):
    import jax
    import numpy as np

    plan, apart, dicts = one_program
    tier = plan.str_tiers[tier_at]
    batch, ns, n_real = _padded_batch(plan, dicts, tier)
    assert n_real < len(ns)
    plan.telemetry.drain(), apart.telemetry.drain()
    packed = plan.packed_check(batch, ns, n_real=n_real)

    narrowed = apart.narrow_batch(batch)
    verdict = apart.engine.check(narrowed, ns)
    apart.telemetry.observe(verdict, ns, np.arange(len(ns)) < n_real)
    want = np.asarray(jax.jit(apart._base_packer())(verdict, ns))

    assert packed.dtype == want.dtype == np.int32
    assert packed.shape == want.shape == (
        5 + plan.n_ref_words + plan.n_overlay_words, len(ns))
    np.testing.assert_array_equal(packed, want)
    got, ref = plan.telemetry.drain(), apart.telemetry.drain()
    for plane in ("hit", "deny", "err"):
        np.testing.assert_array_equal(got[plane], ref[plane])
    # the batch exercised what it says: hits and denials, one of them
    # in the unknown-namespace slot's row set, padding counted nowhere
    assert ref["hit"].sum() > 0 and ref["deny"].sum() > 0
    assert len(set(packed[0, :n_real])) > 1
    assert ref["hit"][-1].sum() > 0 or ref["deny"][-1].sum() > 0
    if plan.fused_deny:            # the rules world errors as well
        assert ref["err"].sum() > 0 and packed[4, 0] > 0


def test_prewarm_compiles_what_is_served_and_counts_nothing(one_program):
    plan, _, dicts = one_program
    plan.telemetry.drain()
    plan.warm_shapes(plan.all_warm_shapes((64,)))
    drained = plan.telemetry.drain()
    assert not any(drained[p].any() for p in ("hit", "deny", "err"))
    # the program's own jit cache, not compile_cache.cache_event_counts:
    # that one is process-wide, and other tests' servers warm in the
    # background (the benchmark's compiles_in_window reads it, alone
    # in its process)
    compiled = plan._step._cache_size()
    for tier in plan.str_tiers:
        batch, ns, n_real = _padded_batch(plan, dicts, tier)
        plan.packed_check(batch, ns, n_real=n_real)
    assert plan._step._cache_size() == compiled == len(plan.str_tiers)
    assert plan.telemetry.drain()["hit"].sum() > 0
    # nothing was launched behind the step
    assert plan._packer is None and plan.cache_stats()[
        "step_entries"] == compiled


def test_unobserved_trip_leaves_every_observer_untouched(one_program):
    import numpy as np

    from istio_tpu.runtime import monitor

    plan, _, dicts = one_program
    batch, ns, n_real = _padded_batch(plan, dicts, plan.str_tiers[0])
    plan.telemetry.drain()
    served = dict(plan._shape_served), dict(plan._tier_served)
    programs = monitor.device_program_counters()
    base = monitor.stage_baseline()
    quiet = plan.packed_check(batch, ns, observe=False, n_real=n_real)
    assert (dict(plan._shape_served), dict(plan._tier_served)) == served
    assert monitor.device_program_counters() == programs
    seen = monitor.latency_snapshot(since=base)
    assert not seen["stages"] and not seen["spans"]
    drained = plan.telemetry.drain()
    assert not any(drained[p].any() for p in ("hit", "deny", "err"))
    # the verdict itself is the served one
    np.testing.assert_array_equal(
        quiet, plan.packed_check(batch, ns, n_real=n_real))
    assert monitor.device_program_counters()["check"] == \
        programs["check"] + 1


def test_neither_prewarm_nor_a_compile_holds_the_telemetry_lock(
        one_program):
    """The accumulators' lock is what the other pump and the drain wait
    on: an unobserved trip never takes it, and the first trip at a
    shape compiles before it does."""
    import threading
    import time

    _, fresh, dicts = one_program     # has launched no program yet
    batch, ns, n_real = _padded_batch(fresh, dicts, fresh.str_tiers[0])
    assert fresh._step is None
    done = []

    def trip(observe):
        fresh.packed_check(batch, ns, observe=observe, n_real=n_real)
        done.append(observe)

    served = threading.Thread(target=trip, args=(True,))
    with fresh.telemetry._lock:
        served.start()               # compiles, then waits for the lock
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and not (
                fresh._step is not None and fresh._step._cache_size()):
            time.sleep(0.01)
        assert fresh._step._cache_size() == 1 and not done
        quiet = threading.Thread(target=trip, args=(False,))
        quiet.start()
        quiet.join(timeout=120)      # runs to its end under our lock
        assert done == [False]
    served.join(timeout=120)
    assert done == [False, True] and not served.is_alive()
    assert fresh.telemetry.drain()["hit"].sum() > 0


def test_report_rows_ride_the_check_program_and_count_nothing(one_program):
    """packed_report off a mesh: the Check program's packed rows with
    the field planes appended to its handle equal the step + report
    packer launched apart (the mesh path), the plain step is never
    compiled for it, and Report traffic feeds no rule counter."""
    import jax
    import numpy as np

    from istio_tpu.runtime import monitor

    plan, apart, dicts = one_program
    batch, ns, _ = _padded_batch(plan, dicts, plan.str_tiers[0])
    plan.telemetry.drain()
    programs = monitor.device_program_counters()
    got = plan.packed_report(batch, ns)
    narrowed = apart.narrow_batch(batch)
    verdict = apart.engine.check(narrowed, ns)
    if plan.report_lowering is not None and plan.report_lowering.n_fields:
        want = jax.jit(apart._base_report_packer())(verdict, ns, narrowed)
        assert got.shape[0] > 5 + plan.n_ref_words + plan.n_overlay_words
    else:                      # no field lowered: the check rows alone
        want = jax.jit(apart._base_packer())(verdict, ns)
    np.testing.assert_array_equal(got, np.asarray(want))
    drained = plan.telemetry.drain()
    assert not any(drained[p].any() for p in ("hit", "deny", "err"))
    assert monitor.device_program_counters() == programs
    assert plan.engine._step._cache_size() == 0
