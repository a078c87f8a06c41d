"""The `routelong*` deployments: `routematch*`'s route table under the
header sizes a sidecar really forwards, and a plain reference.

The table is routematch.py's, taken from that file by its path and not
copied: the same `rule_specs`, `match_block` and `make_store`, so the
two deployments hold the same automata in the same banks and differ in
their requests alone. What differs is how long a request's subjects
are. RFC 6265 s6.1 has user agents hold at least 4 096 bytes a cookie
and Envoy takes 60 KiB of request headers by default; a session cookie
that carries a signed token is 0.5-2 KB. The length distribution is
assumed (the configuration's `assumed.string_lengths`):

    cookie (of the requests that carry one)   request.path (all)
      short     48-112   50 %   routematch's    short   24-72   75 %
      medium   129-480   34.3 % + _ga, _gid,    medium 129-400  20 %
                                consent=<b64>   long   513-1000  5 %
      long    513-2000   15 %   + auth=<b64url>
      oversize 4100-6000  0.7 % several such

`user=group<n>` sits at a position drawn uniformly among the cookie's
parts (the parts are sent in a drawn order), `;`-separated with no
blank, so in a long cookie it lies past byte 128 or 512 about as often
as before it. A long request line is given only
where it leaves the request's class as it is: a family-3 block's
`(/.*)?` full-matches tail segments, and a line of class `none`, which
no block of its host matches, takes a query string; the rate among
those is set so that 20 % + 5 % hold over all requests (as
routematch.free_rates does for the headers).

The reference below reads the same `MatchCondition` dicts with
`re.fullmatch`, `==` and `in` on the WHOLE strings: no expression
parser, nothing of istio_tpu, no byte plane to truncate them.
"""
from __future__ import annotations

import functools
import importlib.util
import re
from pathlib import Path

import numpy as np


def _sibling(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_config_{name}", Path(__file__).with_name(f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


base = _sibling("routematch")

# the table, as routematch10k holds it
WHITELIST, DENIED, NOT_FOUND = base.WHITELIST, base.DENIED, base.NOT_FOUND
host_of, family_of, match_block = \
    base.host_of, base.family_of, base.match_block
rule_specs, make_store = base.rule_specs, base.make_store

# cumulative shares and byte ranges of the length classes
COOKIE_CLASSES = (("short", 0.5, None), ("medium", 0.843, (129, 480)),
                  ("long", 0.993, (513, 2000)),
                  ("oversize", 1.0, (4100, 6000)))
PATH_MEDIUM, PATH_LONG = (0.20, (129, 400)), (0.05, (513, 1000))
B64 = np.frombuffer(b"ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz"
                    b"0123456789+/", np.uint8)
B64URL = np.frombuffer(b"ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwx"
                       b"yz0123456789-_", np.uint8)


def _text(rng, alphabet: np.ndarray, n: int) -> str:
    return alphabet[rng.integers(0, len(alphabet), n)].tobytes().decode()


def _digits(rng, n: int) -> str:
    return "".join(str(d) for d in rng.integers(0, 10, n))


def long_cookie(rng, group: int, kind: str, total: int) -> str:
    """A cookie of exactly `total` bytes: the analytics and consent
    parts a browser carries, a signed token (`long`), several
    (`oversize`), one part's value fitted to the total; the parts,
    `user=group<n>` among them, in a drawn order (a browser sends them
    by path and age, not by name)."""
    user = f"user=group{group}"
    parts = [f"session={base._hex(rng, 32)}"]

    def room() -> int:
        """Bytes left for further parts, their separators included."""
        return total - len(user) - 1 - len(";".join(parts))

    for part in (f"_ga=GA1.2.{_digits(rng, 9)}.{_digits(rng, 10)}",
                 f"_gid=GA1.2.{_digits(rng, 9)}.{_digits(rng, 10)}",
                 f"theme={base.THEMES[int(rng.integers(len(base.THEMES)))]}"):
        if room() > len(part) + 24:       # keep room for a last part
            parts.append(part)
    if kind != "medium":
        size = min(int(rng.integers(80, 301)), room() - 90)
        if size > 0:
            parts.append(f"consent={_text(rng, B64, size)}")
    if kind == "oversize":
        for name in ("auth", "refresh"):
            parts.append(f"{name}={_text(rng, B64URL, int(rng.integers(1400, 1801)))}")
    name = {"medium": "consent", "long": "auth", "oversize": "id_token"}[kind]
    parts.append(f"{name}=" + _text(
        rng, B64 if kind == "medium" else B64URL, room() - len(name) - 2))
    parts.append(user)
    cookie = ";".join(parts[i] for i in rng.permutation(len(parts)))
    assert len(cookie) == total, (kind, total, len(cookie))
    return cookie


def long_path(rng, path: str, tail: bool, total: int) -> str:
    """`path` brought to exactly `total` bytes: tail segments behind a
    family-3 block's `/r<r>` (`tail`), else a query string (a redirect
    URL or a signed query)."""
    if tail:
        path = path.split("/r", 1)[0] + "/r" + \
            path.split("/r", 1)[1].split("/", 1)[0]   # drop a drawn tail
        while total - len(path) > 40:
            path += "/" + base._hex(rng, int(rng.integers(8, 33)))
        return path + "/" + base._hex(rng, total - len(path) - 1)
    path += "?next=https%3A%2F%2Fapp.example.com%2Fhome&sig="
    return path + _text(rng, B64URL, total - len(path))


def path_rates(sizes: dict) -> tuple[float, float]:
    """The rates at which a request whose line may be lengthened gets a
    medium, and a long, one: the shares over all requests divided by
    the share of requests that may (every class `none`; class `one`
    and `two` where the drawn URI block is of family 3)."""
    n_rules, n_services = sizes["rules"], sizes["services"]
    counts = np.zeros((n_services, 4))
    for r in range(n_rules):
        counts[r % n_services, family_of(sizes, r)] += 1
    one, two = base.CLASS_EDGES[0], \
        base.CLASS_EDGES[1] - base.CLASS_EDGES[0]
    may = (1 - base.CLASS_EDGES[1]) + one * counts[:, 3].sum() / n_rules \
        + two * float(np.mean(counts[:, 3]
                              / (counts.sum(axis=1) - counts[:, 2])))
    return PATH_MEDIUM[0] / may, PATH_LONG[0] / may


def make_requests(sizes: dict, n: int, seed: int) -> list[dict]:
    """routematch.make_requests' classes, hosts and header shares
    (three classes by CLASS_EDGES; a cookie on COOKIE_SHARE of all
    requests, x-version on VERSION_SHARE), with the cookie's and the
    request line's lengths drawn as the module's docstring says."""
    rng = np.random.default_rng(seed)
    n_rules, n_services = sizes["rules"], sizes["services"]
    per_host = n_rules // n_services
    cookie_rate, version_rate = base.free_rates(sizes)
    medium_rate, long_rate = path_rates(sizes)
    cookie_edges = [edge for _, edge, _ in COOKIE_CLASSES]

    def block(s: int, want_cookie: bool) -> int:
        js = [j for j in range(per_host)
              if (family_of(sizes, s + j * n_services) == 2) == want_cookie]
        return s + js[int(rng.integers(len(js)))] * n_services

    out = []
    for k in range(n):
        kind = base.CLASSES[int(np.searchsorted(
            base.CLASS_EDGES, rng.random(), side="right"))]
        s = int(rng.integers(n_services))
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
        family = family_of(sizes, uri_block)
        if kind != "none" and family == 3:
            version = f"v{uri_block % 5}"
        headers = {":authority": f"svc{s}"}
        if with_cookie:
            name, _, span = COOKIE_CLASSES[int(np.searchsorted(
                cookie_edges, rng.random(), side="right"))]
            headers["cookie"] = base._cookie(rng, group) if span is None \
                else long_cookie(rng, group, name,
                                 int(rng.integers(span[0], span[1] + 1)))
        if version is not None:
            headers["x-version"] = version
        path = base.path_for(sizes, uri_block, rng, k)
        if kind == "none" or family == 3:
            draw = rng.random()
            span = PATH_MEDIUM[1] if draw < medium_rate else \
                PATH_LONG[1] if draw < medium_rate + long_rate else None
            if span is not None:
                path = long_path(rng, path, family == 3,
                                 int(rng.integers(span[0], span[1] + 1)))
        out.append({
            "destination.service": host_of(sizes, s),
            "source.namespace":
                f"ns{int(rng.integers(sizes['request_source_namespaces']))}",
            "request.method": "GET",
            "request.path": path,
            "request.headers": headers,
        })
    return out


@functools.lru_cache(maxsize=None)
def _pattern(regex: str):
    """re's own cache holds 512 patterns; the table has 10 000."""
    return re.compile(regex)


def _holds(cond: dict, value) -> bool:
    """One header condition of a MatchCondition on the WHOLE value."""
    if value is None:
        return False
    if "exact" in cond and value != cond["exact"]:
        return False
    return "regex" not in cond or \
        _pattern(cond["regex"]).fullmatch(value) is not None


def reference(sizes: dict):
    """expected_status(request) -> int. The blocks of the request's
    host in rule order (a rule applies inside its own namespace, the
    one the host's name carries), every named header condition of a
    block on the whole string (`uri` is the request line; a regex
    full-matches, as Envoy's does); the first status that is not OK
    stands, in rule then action order, as Mixer's combineResults gives
    it where route precedence stood."""
    by_host: dict[str, list] = {}
    for spec in rule_specs(sizes):
        by_host.setdefault(spec["host"], []).append(spec)

    def expected_status(request: dict) -> int:
        dest = request["destination.service"]
        headers = request["request.headers"]
        for spec in by_host.get(dest, ()):
            if spec["namespace"] != dest.split(".")[1]:
                continue
            if not all(_holds(cond, request["request.path"]
                              if name == "uri" else headers.get(name))
                       for name, cond in
                       spec["match"]["request"]["headers"].items()):
                continue
            if spec["deny"]:
                return DENIED
            if spec["whitelist"] and \
                    request["source.namespace"] not in WHITELIST:
                return NOT_FOUND
        return 0

    return expected_status
