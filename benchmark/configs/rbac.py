"""The `rbac*` deployments: ServiceRoles and bindings as real config
kinds, the requests they authorize, and a plain reference.

The store and request generators are COPIES of
istio_tpu/testing/workloads.py (make_rbac_store,
make_rbac_request_dicts); the yardstick may not import them. Roles and
bindings are kept as data (`role_specs`), and the reference below is
istio 0.5 mixer/adapter/rbac/rbac.go HandleAuthorization in plain
string operations — nothing of istio_tpu.
"""
from __future__ import annotations

import numpy as np

DENIED = 7


def role_specs(sizes: dict) -> list[tuple[dict, dict]]:
    """(ServiceRole access rule, binding subject) per role index."""
    n_services, n_users = sizes["services"], sizes["users"]
    out = []
    for i in range(sizes["roles"]):
        k = i % 4
        if k == 0:
            services = [f"svc{i % n_services}.default.svc.cluster.local"]
        elif k == 1:
            services = ["*.default.svc.cluster.local"]
        else:
            services = [f"svc{i % n_services}.*"]
        rule: dict = {"services": services,
                      "methods": (["GET"], ["GET", "POST"], ["*"],
                                  ["DELETE"])[k],
                      "paths": ([f"/api/v{i % 9}/*"], ["*"],
                                [f"*/{i % 31}.html"],
                                [f"/data/{i % 100}"])[k]}
        if i % 5 == 0:
            rule["constraints"] = [{"key": "version",
                                    "values": ["v1", f"v{i % 7}"]}]
        if i % 3 == 0:
            subj: dict = {"user": f"user{i % n_users}"}
        elif i % 3 == 1:
            subj = {"group": f"group{i % 29}"}
        else:   # user AND group
            subj = {"user": f"user{i % n_users}",
                    "group": f"group{i % 29}"}
        if i % 7 == 0:
            subj["properties"] = {"version": f"v{i % 7}"}
        out.append((rule, subj))
    return out


def make_store(sizes: dict):
    from istio_tpu.runtime.store import MemStore

    s = MemStore()
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
    for i, (rule, subj) in enumerate(role_specs(sizes)):
        s.set(("servicerole", "default", f"role{i}"), {"rules": [rule]})
        s.set(("servicerolebinding", "default", f"bind{i}"), {
            "roleRef": {"kind": "ServiceRole", "name": f"role{i}"},
            "subjects": [subj]})
    return s


def make_requests(sizes: dict, n: int, seed: int) -> list[dict]:
    rng = np.random.default_rng(seed)
    n_users, n_services = sizes["users"], sizes["services"]
    n_groups = sizes["request_groups"]
    out = []
    for i in range(n):
        out.append({
            "source.user": f"user{int(rng.integers(n_users))}",
            "source.labels": {"group": f"group{int(rng.integers(n_groups))}",
                              "version": f"v{int(rng.integers(8))}"},
            "destination.namespace": "default",
            "destination.service":
                f"svc{int(rng.integers(n_services))}"
                ".default.svc.cluster.local",
            "request.method": ("GET", "POST", "DELETE",
                               "PUT")[int(rng.integers(4))],
            "request.path": (f"/api/v{int(rng.integers(10))}/items",
                             f"/data/{int(rng.integers(120))}",
                             f"/static/{int(rng.integers(40))}.html"
                             )[i % 3],
            "request.headers": {"version": f"v{int(rng.integers(8))}"},
        })
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
    """expected_status(request) -> int: OK when some binding's subject
    matches the caller and its role's access rule matches the action,
    else PERMISSION_DENIED. All roles live in namespace `default`, as
    every request's destination does."""
    specs = role_specs(sizes)

    def expected_status(request: dict) -> int:
        labels = request["source.labels"]
        version = request["request.headers"]["version"]
        action = {"services": request["destination.service"],
                  "methods": request["request.method"],
                  "paths": request["request.path"]}
        if request["destination.namespace"] != "default":
            return DENIED
        for rule, subj in specs:
            if "user" in subj and subj["user"] != request["source.user"]:
                continue
            if "group" in subj and subj["group"] != labels["group"]:
                continue
            if any(labels.get(k, "") != v
                   for k, v in subj.get("properties", {}).items()):
                continue
            if not all(any(_string_match(p, action[field])
                           for p in rule[field]) for field in action):
                continue
            if all(version in c["values"]
                   for c in rule.get("constraints", ())):
                return 0
        return DENIED

    return expected_status
