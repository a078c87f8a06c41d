"""The `mixer*` deployments: Bookinfo/authz-flavoured rules as real
config kinds, the requests that hit them, and a plain reference.

The store and request generators are COPIES of
istio_tpu/testing/workloads.py (make_rules / make_store with the legacy
fixed constants, make_request_dicts); the yardstick may not import
them. Each rule is kept as data (`rule_specs`), so the reference below
evaluates it with plain string operations and `re` — no expression
parser, nothing of istio_tpu.
"""
from __future__ import annotations

import importlib.util
import re
from pathlib import Path

import numpy as np

WHITELIST = frozenset(f"ns{j}" for j in range(0, 23, 2))
DENIED, NOT_FOUND = 7, 5
QUOTA_MAX = 1 << 30    # rq.istio-system: no key of the traffic exhausts it

# predicate kind -> (match expression, plain evaluation of it)
PREDICATES = {
    "ns_neq": ('source.namespace != "{}"',
               lambda r, c: r["source.namespace"] != c),
    "method_eq": ('request.method == "{}"',
                  lambda r, c: r["request.method"] == c),
    "cookie_eq": ('request.headers["cookie"] == "{}"',
                  lambda r, c: r["request.headers"]["cookie"] == c),
    "mtls": ("connection.mtls", lambda r, c: r["connection.mtls"]),
    "path_prefix": ('request.path.startsWith("{}")',
                    lambda r, c: r["request.path"].startswith(c)),
    # match(): a leading * is a suffix test (istio's glob)
    "host_glob": ('match(request.host, "{}")',
                  lambda r, c: r["request.host"].endswith(c[1:])),
    "path_regex": ('"{}".matches(request.path)',
                   lambda r, c: re.search(c, r["request.path"]) is not None),
}


def rule_specs(sizes: dict) -> list[dict]:
    n_services, n_ns = sizes["services"], sizes["namespaces"]
    out = []
    for i in range(sizes["rules"]):
        ns = i % n_ns
        k = i % 10
        if k < 4:
            pred = ("ns_neq", f"locked{i % 5}")
        elif k == 4:
            pred = ("method_eq", "GET" if i % 2 else "POST")
        elif k == 5:
            pred = ("cookie_eq", f"session={i % 97}")
        elif k == 6:
            pred = ("mtls", "")
        elif k == 7:
            pred = ("path_prefix", f"/api/v{i % 3}/")
        elif k == 8:
            pred = ("host_glob", f"*.ns{ns}.cluster.local")
        else:
            pred = ("path_regex", f"/(products|reviews)/[0-9]+/v{i % 4}")
        out.append({
            "name": f"rule{i}", "namespace": f"ns{ns}",
            "service": f"svc{i % n_services}.ns{ns}.svc.cluster.local",
            "pred": pred,
            "deny": i % sizes["deny_every"] == 0,
            "whitelist": i % sizes["whitelist_every"] == 1})
    return out


def make_store(sizes: dict):
    from istio_tpu.runtime.store import MemStore

    s = MemStore()
    s.set(("handler", "istio-system", "denyall"), {
        "adapter": "denier", "params": {"status_code": DENIED}})
    s.set(("handler", "istio-system", "nswhitelist"), {
        "adapter": "list",
        "params": {"overrides": sorted(WHITELIST), "blacklist": False}})
    s.set(("handler", "istio-system", "mq"), {
        "adapter": "memquota",
        "params": {"quotas": [{"name": "rq.istio-system",
                               "max_amount": QUOTA_MAX}]}})
    s.set(("instance", "istio-system", "rq"), {
        "template": "quota",
        "params": {"dimensions": {"user": 'source.user | "anon"'}}})
    s.set(("rule", "istio-system", "quota-rule"), {
        "match": "",
        "actions": [{"handler": "mq", "instances": ["rq"]}]})
    s.set(("instance", "istio-system", "nothing"), {
        "template": "checknothing", "params": {}})
    s.set(("instance", "istio-system", "srcns"), {
        "template": "listentry", "params": {"value": "source.namespace"}})
    s.set(("handler", "istio-system", "prom"), {
        "adapter": "prometheus",
        "params": {"metrics": [{
            "name": "reqcount.istio-system", "kind": "COUNTER",
            "label_names": ["destination"]}]}})
    s.set(("instance", "istio-system", "reqcount"), {
        "template": "metric",
        "params": {"value": "1",
                   "dimensions": {"destination":
                                  'destination.service | "unknown"'}}})
    s.set(("rule", "istio-system", "report-all"), {
        "match": "",
        "actions": [{"handler": "prom", "instances": ["reqcount"]}]})
    for spec in rule_specs(sizes):
        kind, const = spec["pred"]
        match = (f'destination.service == "{spec["service"]}" && '
                 + PREDICATES[kind][0].format(const))
        actions = []
        if spec["deny"]:
            actions.append({"handler": "denyall.istio-system",
                            "instances": ["nothing.istio-system"]})
        if spec["whitelist"]:
            actions.append({"handler": "nswhitelist.istio-system",
                            "instances": ["srcns.istio-system"]})
        if not actions:   # every rule carries at least a no-op check
            actions.append({"handler": "denyall.istio-system",
                            "instances": []})
        s.set(("rule", spec["namespace"], spec["name"]),
              {"match": match, "actions": actions})
    return s


def make_requests(sizes: dict, n: int, seed: int) -> list[dict]:
    rng = np.random.default_rng(seed)
    n_ns = sizes["namespaces"]
    dicts = []
    for _ in range(n):
        i = int(rng.integers(0, 4096))
        dicts.append({
            "destination.service":
                f"svc{rng.integers(0, sizes['request_services'])}"
                f".ns{i % n_ns}.svc.cluster.local",
            "source.namespace":
                f"ns{rng.integers(0, sizes['request_source_namespaces'])}",
            "source.user": f"cluster.local/ns/ns{i % n_ns}/sa/sa{i % 61}",
            "request.method": "GET" if rng.random() < 0.7 else "POST",
            "request.path": f"/api/v{rng.integers(0, 4)}/products/{i}",
            "request.host": f"svc{i % 31}.ns{i % n_ns}.cluster.local",
            "request.size": i,
            "connection.mtls": bool(rng.random() < 0.5),
            "request.headers": {"cookie": f"session={rng.integers(0, 120)}",
                                ":authority": "productpage"},
        })
    return dicts


def reference(sizes: dict):
    """expected_status(request) -> int. A rule applies to a request
    whose destination.service names the rule's namespace
    (svc.NS.svc.cluster.local); statuses combine as Mixer's
    combineResults does: the first non-OK in rule, then action, order."""
    by_service: dict[str, list] = {}
    for spec in rule_specs(sizes):
        by_service.setdefault(spec["service"], []).append(spec)

    def expected_status(request: dict) -> int:
        dest = request["destination.service"]
        ns = dest.split(".")[1]
        for spec in by_service.get(dest, ()):
            kind, const = spec["pred"]
            if spec["namespace"] != ns or \
                    not PREDICATES[kind][1](request, const):
                continue
            if spec["deny"]:
                return DENIED
            if spec["whitelist"] and \
                    request["source.namespace"] not in WHITELIST:
                return NOT_FOUND
        return 0

    return expected_status


def _sibling(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_config_{name}", Path(__file__).with_name(f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def quota_reference(sizes: dict):
    """A fresh plain memquota for `sizes["quota_name"]`, as make_store
    configures it: the mesh-wide rule (match "", so it serves every
    request), one counter a `source.user | "anon"`, QUOTA_MAX and no
    valid_duration: an exact counter that never expires."""
    return _sibling("memquota_plain").MemQuota(
        sizes["quota_name"], QUOTA_MAX, reference(sizes),
        key_of=lambda request: request.get("source.user", "anon"))
