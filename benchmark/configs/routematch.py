"""The `routematch*` deployments: a mesh's route table as policy — one
Mixer rule per VirtualService match block, every block with its own
header / URI pattern — the requests such a table is matched against,
and a plain reference.

BASELINE.json config 3 ("Pilot RouteRule match: 10k VirtualService
header/URI regex predicates"). A match block is kept as data, a
`MatchCondition`-shaped dict (istio 0.5 routing/v1alpha1: uri / header
x exact / prefix / regex), and lowered to a rule's `match` by Pilot's
own `istio_tpu.pilot.route_nfa.match_to_predicate`, so a change to that
lowering is felt here. Route regexes are written as a route table
writes them: unanchored, because Envoy full-matches; the cookie form is
Bookinfo's `^(.*?;)?(user=jason)(;.*)?$`, kept anchored. The reference
below reads the same dicts with `re.fullmatch`, `==` and `in` — no
expression parser, nothing of istio_tpu.

Block r belongs to service s = r mod services and is the service's
j-th operation, j = r div services; its family is (j + s) mod 4, so
every host holds URI blocks and cookie blocks both (the two-match
request class needs a host with each), and every pattern carries r: no
two automata of the table are alike.
"""
from __future__ import annotations

import re

import numpy as np

WHITELIST = frozenset(f"ns{j}" for j in range(0, 23, 2))
DENIED, NOT_FOUND = 7, 5
RESOURCES = ("items", "orders", "users", "carts", "invoices")
# make_requests: cumulative shares of the three traffic classes
CLASSES = ("one", "two", "none")
CLASS_EDGES = (0.5, 0.6, 1.0)
# make_requests: the share of all requests that carry a cookie, and an
# x-version header (a class that needs one counts towards the share)
COOKIE_SHARE, VERSION_SHARE = 0.75, 0.5
THEMES = ("dark", "light", "solar", "contrast")
HEX = "0123456789abcdef"


def host_of(sizes: dict, s: int) -> str:
    return f"svc{s}.ns{s % sizes['namespaces']}.svc.cluster.local"


def family_of(sizes: dict, r: int) -> int:
    return (r // sizes["services"] + r % sizes["services"]) % 4


def match_block(sizes: dict, r: int) -> dict:
    """Block r's `MatchCondition`: headers by name, `uri` the path."""
    family = family_of(sizes, r)
    if family == 0:
        headers = {"uri": {"regex": f"/api/v{r % 9}/r{r}/"
                           f"{RESOURCES[r % len(RESOURCES)]}/[0-9]+"}}
    elif family == 1:
        headers = {"uri": {
            "regex": f"/r{r}/(products|reviews)/[0-9]+/v{r % 4}"}}
    elif family == 2:
        headers = {"cookie": {"regex": f"^(.*?;)?(user=group{r})(;.*)?$"}}
    else:
        headers = {"uri": {"regex": f"/v{r % 3}/t/[a-z0-9-]+/r{r}(/.*)?"},
                   "x-version": {"exact": f"v{r % 5}"}}
    return {"request": {"headers": headers}}


def rule_specs(sizes: dict) -> list[dict]:
    out = []
    for r in range(sizes["rules"]):
        s = r % sizes["services"]
        out.append({
            "name": f"route{r}", "namespace": f"ns{s % sizes['namespaces']}",
            "host": host_of(sizes, s), "match": match_block(sizes, r),
            "deny": r % sizes["deny_every"] == 0,
            "whitelist": r % sizes["whitelist_every"] == 1})
    return out


def make_store(sizes: dict):
    from istio_tpu.pilot.route_nfa import match_to_predicate
    from istio_tpu.runtime.store import MemStore

    s = MemStore()
    s.set(("handler", "istio-system", "denyall"), {
        "adapter": "denier", "params": {"status_code": DENIED}})
    s.set(("handler", "istio-system", "nswhitelist"), {
        "adapter": "list",
        "params": {"overrides": sorted(WHITELIST), "blacklist": False}})
    s.set(("instance", "istio-system", "nothing"), {
        "template": "checknothing", "params": {}})
    s.set(("instance", "istio-system", "srcns"), {
        "template": "listentry", "params": {"value": "source.namespace"}})
    for spec in rule_specs(sizes):
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
        s.set(("rule", spec["namespace"], spec["name"]), {
            "match": match_to_predicate(spec["host"], spec["match"]),
            "actions": actions})
    return s


def _digits(rng, k: int) -> str:
    """A numeric id of drawn length that ends in the request's index."""
    head = "".join(str(d) for d in rng.integers(0, 10, rng.integers(5, 20)))
    return f"{head}{k:05d}"


def _hex(rng, n: int) -> str:
    return "".join(HEX[d] for d in rng.integers(0, 16, n))


def path_for(sizes: dict, r: int, rng, k: int) -> str:
    """A request line block r's URI pattern full-matches; it carries
    the request's index k. Cookie blocks have no URI: a bare id."""
    family = family_of(sizes, r)
    if family == 0:
        return (f"/api/v{r % 9}/r{r}/{RESOURCES[r % len(RESOURCES)]}/"
                f"{_digits(rng, k)}")
    if family == 1:
        kind = "products" if rng.random() < 0.5 else "reviews"
        return f"/r{r}/{kind}/{_digits(rng, k)}/v{r % 4}"
    if family == 3:
        slug = f"t-{_hex(rng, int(rng.integers(8, 29)))}-{k:05d}"
        tail = f"/{_hex(rng, int(rng.integers(4, 17)))}" \
            if rng.random() < 0.5 else ""
        return f"/v{r % 3}/t/{slug}/r{r}{tail}"
    return f"/static/assets/{_digits(rng, k)}"


def _cookie(rng, group: int) -> str:
    """48-112 bytes, `;`-separated with no blank (Bookinfo's pattern
    wants `user=` right behind the `;`)."""
    parts = [f"session={_hex(rng, 32)}", f"user=group{group}",
             f"theme={THEMES[int(rng.integers(len(THEMES)))]}"]
    if rng.random() < 0.5:
        parts.append(f"trk={_hex(rng, int(rng.integers(4, 33)))}")
    return ";".join(parts)


def free_rates(sizes: dict) -> tuple[float, float]:
    """The rates at which make_requests gives a request a cookie, and
    an x-version, where its class does not decide: set so that over
    all requests COOKIE_SHARE and VERSION_SHARE hold. A class decides
    where the block it is built to match reads the header: every
    two-match request and a one-match request of a cookie block carry
    the cookie, a request built for a family-3 block its x-version."""
    n_rules, n_services = sizes["rules"], sizes["services"]
    counts = np.zeros((n_services, 4))
    for r in range(n_rules):
        counts[r % n_services, family_of(sizes, r)] += 1
    one, two = CLASS_EDGES[0], CLASS_EDGES[1] - CLASS_EDGES[0]
    # "one" draws one of all blocks uniformly; "two" one of the
    # host's URI blocks
    cookie = two + one * counts[:, 2].sum() / n_rules
    version = one * counts[:, 3].sum() / n_rules + two * float(np.mean(
        counts[:, 3] / (counts.sum(axis=1) - counts[:, 2])))
    return (float((COOKIE_SHARE - cookie) / (1 - cookie)),
            float((VERSION_SHARE - version) / (1 - version)))


def make_requests(sizes: dict, n: int, seed: int) -> list[dict]:
    """Three classes (CLASS_EDGES), drawn from `seed`: exactly one of
    the host's blocks matches, two do (a URI block and a cookie block),
    or none (a well-formed request line of another host's operation).
    A cookie on COOKIE_SHARE of all requests, x-version on
    VERSION_SHARE (free_rates). The path carries the request's index,
    so all n are distinct."""
    rng = np.random.default_rng(seed)
    n_rules, n_services = sizes["rules"], sizes["services"]
    per_host = n_rules // n_services
    cookie_rate, version_rate = free_rates(sizes)

    def block(s: int, want_cookie: bool) -> int:
        """One of host s's blocks, of the cookie family or not."""
        js = [j for j in range(per_host)
              if (family_of(sizes, s + j * n_services) == 2) == want_cookie]
        return s + js[int(rng.integers(len(js)))] * n_services

    out = []
    for k in range(n):
        kind = CLASSES[int(np.searchsorted(CLASS_EDGES, rng.random(),
                                           side="right"))]
        s = int(rng.integers(n_services))
        # a group no block names, a version drawn at large
        group = n_rules + int(rng.integers(n_rules))
        with_cookie = rng.random() < cookie_rate
        version = f"v{int(rng.integers(5))}" \
            if rng.random() < version_rate else None
        if kind == "none":
            other = (s + 1 + int(rng.integers(n_services - 1))) % n_services
            uri_block = block(other, False)
        elif kind == "two":
            uri_block, group, with_cookie = block(s, False), \
                block(s, True), True
        else:
            chosen = s + int(rng.integers(per_host)) * n_services
            if family_of(sizes, chosen) == 2:
                uri_block, group, with_cookie = chosen, chosen, True
            else:
                uri_block = chosen
        if kind != "none" and family_of(sizes, uri_block) == 3:
            version = f"v{uri_block % 5}"
        headers = {":authority": f"svc{s}"}
        if with_cookie:
            headers["cookie"] = _cookie(rng, group)
        if version is not None:
            headers["x-version"] = version
        out.append({
            "destination.service": host_of(sizes, s),
            "source.namespace":
                f"ns{int(rng.integers(sizes['request_source_namespaces']))}",
            "request.method": "GET",
            "request.path": path_for(sizes, uri_block, rng, k),
            "request.headers": headers,
        })
    return out


def _block_matches(match: dict, request: dict) -> bool:
    """MatchCondition: every named header condition holds; `uri` is
    the request line; a regex full-matches, as Envoy's does."""
    headers = request["request.headers"]
    for name, cond in match["request"]["headers"].items():
        value = request["request.path"] if name == "uri" \
            else headers.get(name)
        if value is None:
            return False
        if "exact" in cond and value != cond["exact"]:
            return False
        if "regex" in cond and re.fullmatch(cond["regex"], value) is None:
            return False
    return True


def reference(sizes: dict):
    """expected_status(request) -> int. The blocks of the request's
    host in rule order (a rule applies inside its own namespace, which
    is the one the host's name carries); the first status that is not
    OK stands, in rule then action order, as Mixer's combineResults
    gives it where route precedence stood."""
    by_host: dict[str, list] = {}
    for spec in rule_specs(sizes):
        by_host.setdefault(spec["host"], []).append(spec)

    def expected_status(request: dict) -> int:
        dest = request["destination.service"]
        ns = dest.split(".")[1]
        for spec in by_host.get(dest, ()):
            if spec["namespace"] != ns or \
                    not _block_matches(spec["match"], request):
                continue
            if spec["deny"]:
                return DENIED
            if spec["whitelist"] and \
                    request["source.namespace"] not in WHITELIST:
                return NOT_FOUND
        return 0

    return expected_status
