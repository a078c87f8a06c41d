"""The `fullmesh*` deployments: one SAN whitelist per service, the
mesh's ServiceRoles and bindings, and a mesh-wide quota, as real config
kinds in ONE snapshot; the requests a strict-mTLS mesh sends; and a
plain reference.

The sizes and the role / SAN patterns are those of
istio_tpu/testing/workloads.py make_full_mesh (BASELINE config 5),
which hand-builds a PolicyEngine; here the same deployment is a store,
so it is served as any other. The yardstick may not import that
module. Its route-NFA rows are left out: Mixer's Check selects no
route (Envoy does). Services, SANs, roles and bindings are kept as
data (`service_name`, `sans_of`, `role_specs`), and the reference below
is istio 0.5 mixer/adapter/list/list.go and mixer/adapter/rbac/rbac.go
in plain string operations — nothing of istio_tpu.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

NOT_FOUND, DENIED = 5, 7
QUOTA_MAX = 1 << 24    # rq.istio-system: no key of the traffic exhausts it
# make_requests: cumulative shares of the four traffic classes
CLASSES = ("conformant", "wrong_san", "no_role", "plain_text")
CLASS_EDGES = (0.7, 0.8, 0.9, 1.0)


def service_name(sizes: dict, i: int) -> str:
    return f"svc{i}.ns{i % sizes['namespaces']}.svc.cluster.local"


def spiffe(ns: int, sa: int) -> str:
    return f"spiffe://cluster.local/ns/ns{ns}/sa/sa{sa}"


def sans_of(sizes: dict, i: int) -> list[str]:
    """The identities service i accepts: its namespace's service
    accounts."""
    return [spiffe(i % sizes["namespaces"], j)
            for j in range(sizes["sans_per_service"])]


def role_specs(sizes: dict) -> list[tuple[dict, dict]]:
    """(ServiceRole access rule, binding subject) per role index: role r
    covers service r for one of that service's own SANs."""
    return [({"services": [f"svc{r}.*"],
              "methods": (["GET"], ["GET", "POST"], ["*"])[r % 3],
              "paths": [f"/api/v{r % 9}/*"]},
             {"user": spiffe(r % sizes["namespaces"], r % 3)})
            for r in range(sizes["roles"])]


def make_store(sizes: dict):
    from istio_tpu.runtime.store import MemStore

    s = MemStore()
    s.set(("instance", "istio-system", "srcuser"), {
        "template": "listentry", "params": {"value": "source.user"}})
    # rules are served in the order they are written: the SAN rules,
    # the quota rule, the authz rule (make_full_mesh's row layout)
    for i in range(sizes["services"]):
        ns = f"ns{i % sizes['namespaces']}"
        s.set(("handler", ns, f"san{i}"), {
            "adapter": "list",
            "params": {"overrides": sans_of(sizes, i),
                       "blacklist": False}})
        s.set(("rule", ns, f"san{i}"), {
            "match": f'destination.service == "{service_name(sizes, i)}"'
                     " && connection.mtls",
            "actions": [{"handler": f"san{i}",
                         "instances": ["srcuser.istio-system"]}]})
    s.set(("handler", "istio-system", "mq"), {
        "adapter": "memquota",
        "params": {"quotas": [{"name": "rq.istio-system",
                               "max_amount": QUOTA_MAX}]}})
    s.set(("instance", "istio-system", "rq"), {
        "template": "quota",
        "params": {"dimensions": {"user": 'source.user | "anon"'}}})
    s.set(("rule", "istio-system", "quota-rule"), {
        "match": "connection.mtls",
        "actions": [{"handler": "mq", "instances": ["rq"]}]})
    s.set(("handler", "istio-system", "authzh"), {
        "adapter": "rbac", "params": {"caching_ttl_s": 60.0}})
    s.set(("instance", "istio-system", "authz"), {
        "template": "authorization",
        "params": {
            "subject": {"user": 'source.user | ""',
                        "groups": 'source.labels["group"] | ""',
                        "properties": {
                            "version": 'source.labels["version"] | ""'}},
            "action": {"namespace": 'destination.namespace | ""',
                       "service": 'destination.service | ""',
                       "method": 'request.method | ""',
                       "path": 'request.path | ""',
                       "properties": {
                           "version":
                               'request.headers["version"] | ""'}}}})
    s.set(("rule", "istio-system", "authz-rule"), {
        "match": "", "actions": [{"handler": "authzh",
                                  "instances": ["authz"]}]})
    for r, (rule, subj) in enumerate(role_specs(sizes)):
        s.set(("servicerole", "default", f"role{r}"), {"rules": [rule]})
        s.set(("servicerolebinding", "default", f"bind{r}"), {
            "roleRef": {"kind": "ServiceRole", "name": f"role{r}"},
            "subjects": [subj]})
    return s


def make_requests(sizes: dict, n: int, seed: int) -> list[dict]:
    """Four classes (CLASS_EDGES), drawn from `seed`; the path carries
    the request's index, so all n are distinct."""
    rng = np.random.default_rng(seed)
    n_services, n_roles = sizes["services"], sizes["roles"]
    n_ns, n_sans = sizes["namespaces"], sizes["sans_per_service"]
    out = []
    for k in range(n):
        kind = CLASSES[int(np.searchsorted(CLASS_EDGES, rng.random(),
                                           side="right"))]
        x = int(rng.integers(n_roles if kind == "conformant"
                             else n_services))
        request = {
            "destination.service": service_name(sizes, x),
            "destination.namespace": "default",
            "connection.mtls": kind != "plain_text",
            "request.method": "GET",
            "request.path": f"/api/v{x % 9}/items/{k}",
        }
        if kind == "conformant":     # the binding's own subject
            request["source.user"] = spiffe(x % n_ns, x % 3)
        elif kind == "wrong_san":    # an identity of another namespace
            other = (x + 1 + int(rng.integers(n_ns - 1))) % n_ns
            request["source.user"] = spiffe(other,
                                            int(rng.integers(n_sans)))
        elif kind == "no_role":      # a listed identity, no role's action
            request["source.user"] = spiffe(x % n_ns,
                                            int(rng.integers(n_sans)))
            request["request.method"] = "DELETE"
            request["request.path"] = f"/admin/{k}"
        if rng.random() < 0.75:
            request["request.headers"] = {
                "cookie": f"session={int(rng.integers(120))}"}
        out.append(request)
    return out


def _string_match(pattern: str, value: str) -> bool:
    """rbac.go stringMatch: exact, `*`, prefix* or *suffix."""
    if pattern == "*":
        return True
    if pattern.endswith("*"):
        return value.startswith(pattern[:-1])
    if pattern.startswith("*"):
        return value.endswith(pattern[1:])
    return pattern == value


def reference(sizes: dict):
    """expected_status(request) -> int. The rules that apply, in the
    order the store holds them: the destination's SAN rule, then the
    mesh's authorization rule; the first status that is not OK stands
    (Mixer 0.5 runs a request's actions concurrently and keeps the
    first non-OK result it combines; this system fixes that order as
    rule, then action, and so does its host oracle). The quota rule
    is not a precondition and decides no Check status."""
    sans = {service_name(sizes, i): frozenset(sans_of(sizes, i))
            for i in range(sizes["services"])}
    specs = role_specs(sizes)

    def list_status(request: dict) -> int:
        # list.go HandleListEntry, whitelist of STRINGS: a value that
        # is not among the overrides is NOT_FOUND. The rule's match
        # needs mTLS, and applies only inside its own namespace, which
        # is the one the service's name carries (resolver.go
        # destAndNamespace), so no other service's rule can apply.
        accepted = sans.get(request["destination.service"])
        if accepted is None or not request["connection.mtls"]:
            return 0
        # every mTLS request of the traffic carries source.user; an
        # absent value is an instance error (INTERNAL) upstream
        return 0 if request["source.user"] in accepted else NOT_FOUND

    def rbac_status(request: dict) -> int:
        # rbac.go HandleAuthorization: allowed when some role of the
        # action's namespace has an access rule that matches the
        # action and a binding whose subject matches the caller. Here
        # every role and binding is in `default`, a role has one rule
        # and no constraint, a binding one subject with a user alone.
        if request["destination.namespace"] != "default":
            return DENIED
        user = request.get("source.user", "")   # the instance's | ""
        action = {"services": request["destination.service"],
                  "methods": request["request.method"],
                  "paths": request["request.path"]}
        for rule, subj in specs:
            if subj["user"] == user and all(
                    any(_string_match(p, action[field])
                        for p in rule[field]) for field in action):
                return 0
        return DENIED

    def expected_status(request: dict) -> int:
        return list_status(request) or rbac_status(request)

    return expected_status


def _sibling(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_config_{name}", Path(__file__).with_name(f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def quota_reference(sizes: dict):
    """A fresh plain memquota for `sizes["quota_name"]`, as make_store
    configures it: the rule serves mTLS requests alone (a plain-text
    one that passed its precondition would be granted freely), one
    counter a `source.user | "anon"`, QUOTA_MAX and no valid_duration:
    an exact counter that never expires."""
    return _sibling("memquota_plain").MemQuota(
        sizes["quota_name"], QUOTA_MAX, reference(sizes),
        key_of=lambda request: request.get("source.user", "anon"),
        rule_matches=lambda request: request["connection.mtls"])
