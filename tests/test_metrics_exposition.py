"""Prometheus text-exposition conformance for the homegrown registry
(utils/metrics.py) — the half of the merged /metrics surface that does
NOT come from prometheus_client and so gets no conformance for free.

Lint contract (ISSUE satellite): every Histogram family must emit
`_bucket` lines ending in le="+Inf", a `_sum` line and a `_count` line
— for every label set it has seen, AND as an explicit zero series when
it has seen none (a bare `# TYPE` line with no samples is a malformed
family to real scrapers).
"""
import re

import numpy as np

from istio_tpu.utils.metrics import (Counter, Gauge, Histogram,
                                     Registry, SlidingWindow)

_SAMPLE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(\{(?P<labels>.*)\})?\s+(?P<value>\S+)$")
# one label pair; the value escaped as the text format asks (\\, \", \n),
# so it may hold commas, braces and quotes
_PAIR = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"(,|$)')
_UNESCAPE = {"\\\\": "\\", '\\"': '"', "\\n": "\n"}


def _parse(text: str):
    """exposition text → {metric name: [(labels dict, float value)]}"""
    out: dict = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        assert m, f"malformed sample line: {line!r}"
        labels = {}
        at, body = 0, m.group("labels") or ""
        while at < len(body):
            pair = _PAIR.match(body, at)
            assert pair, line
            labels[pair.group(1)] = re.sub(
                r'\\[\\"n]', lambda e: _UNESCAPE[e.group()], pair.group(2))
            at = pair.end()
        out.setdefault(m.group("name"), []).append(
            (labels, float(m.group("value"))))
    return out


def _histogram_families(samples: dict) -> set:
    return {n[:-len("_bucket")] for n in samples if n.endswith("_bucket")}


def lint_histograms(text: str, expect: set | None = None) -> None:
    """Assert the satellite's conformance contract over an exposition
    blob; `expect` adds the requirement that those families appear."""
    samples = _parse(text)
    fams = _histogram_families(samples)
    if expect is not None:
        missing = expect - fams
        assert not missing, f"histogram families absent: {missing}"
    for fam in fams:
        buckets = samples[fam + "_bucket"]
        sums = samples.get(fam + "_sum")
        counts = samples.get(fam + "_count")
        assert sums, f"{fam}: no _sum line"
        assert counts, f"{fam}: no _count line"
        # group bucket lines per label set (minus le)
        by_series: dict = {}
        for labels, value in buckets:
            le = labels.get("le")
            assert le is not None, f"{fam}: bucket without le"
            key = tuple(sorted((k, v) for k, v in labels.items()
                               if k != "le"))
            by_series.setdefault(key, []).append((le, value))
        count_by = {tuple(sorted(lb.items())): v for lb, v in counts}
        for key, series in by_series.items():
            les = [le for le, _ in series]
            assert les[-1] == "+Inf", \
                f"{fam}{dict(key)}: bucket ladder must end at +Inf " \
                f"(got {les})"
            vals = [v for _, v in series]
            assert vals == sorted(vals), \
                f"{fam}{dict(key)}: cumulative buckets not monotone"
            assert key in count_by, f"{fam}: _count missing for {key}"
            assert vals[-1] == count_by[key], \
                f"{fam}{dict(key)}: +Inf bucket != _count"


def test_observed_histogram_conformance():
    reg = Registry()
    h = reg.histogram("lat_seconds", "latency", buckets=(0.001, 0.01, 1.0))
    for v in (0.0005, 0.005, 0.5, 2.0):
        h.observe(v)
        h.observe(v, stage="device")
    text = reg.expose_text()
    lint_histograms(text, expect={"lat_seconds"})
    samples = _parse(text)
    # per-series counts: 4 observations each for {} and {stage=device}
    counts = dict((tuple(sorted(lb.items())), v)
                  for lb, v in samples["lat_seconds_count"])
    assert counts[()] == 4
    assert counts[(("stage", "device"),)] == 4
    # sum carries through
    sums = dict((tuple(sorted(lb.items())), v)
                for lb, v in samples["lat_seconds_sum"])
    assert abs(sums[()] - 2.5055) < 1e-9


def test_unobserved_histogram_emits_zero_series():
    """The conformance fix this PR ships: an unobserved histogram used
    to expose only its # TYPE header — no samples at all."""
    reg = Registry()
    reg.histogram("never_seen_seconds", "nothing yet",
                  buckets=(0.1, 1.0))
    text = reg.expose_text()
    lint_histograms(text, expect={"never_seen_seconds"})
    samples = _parse(text)
    assert samples["never_seen_seconds_count"] == [({}, 0.0)]
    assert samples["never_seen_seconds_sum"] == [({}, 0.0)]
    inf = [v for lb, v in samples["never_seen_seconds_bucket"]
           if lb.get("le") == "+Inf"]
    assert inf == [0.0]


def test_counter_gauge_exposition_and_help():
    reg = Registry()
    c = reg.counter("reqs_total", "requests")
    g = reg.gauge("depth", "queue depth")
    c.inc(3, front="grpc")
    g.set(7.5)
    text = reg.expose_text()
    assert "# HELP reqs_total requests" in text
    assert "# TYPE reqs_total counter" in text
    assert 'reqs_total{front="grpc"} 3.0' in text
    assert "depth 7.5" in text


def test_a_label_value_with_quotes_and_commas_reads_back():
    """A DFA bank's subject is an expression (monitor.note_dfa_banks):
    the exposition escapes what the text format asks, so a scraper
    reads the value back whole."""
    r = Registry()
    g = r.gauge("mixer_dfa_bank_bytes", "resident bytes")
    subject = 'OR(INDEX($request.headers, "cookie"), " absent ")'
    odd = 'a\\b}\n{c="d",'
    g.set(4096, subject=subject)
    g.set(7, subject=odd, tier="onehot")
    text = r.expose_text()
    assert '\\"cookie\\"' in text
    assert len(text.splitlines()) == 4      # the newline is escaped
    assert _parse(text)["mixer_dfa_bank_bytes"] == [
        ({"subject": subject}, 4096.0),
        ({"subject": odd, "tier": "onehot"}, 7.0)]


def test_runtime_monitor_registry_lints():
    """The real serving registry (stage decomposition, e2e, live
    gauges) passes the same lint — including before any traffic, when
    every family must still emit its zero series."""
    from istio_tpu.runtime import monitor
    from istio_tpu.utils.metrics import default_registry

    monitor.refresh_latency_gauges()
    text = default_registry.expose_text()
    lint_histograms(text, expect={"mixer_check_stage_seconds",
                                  "mixer_check_e2e_seconds"})
    assert "mixer_check_p99_ms" in text
    assert "check_p99_under_target" in text


def test_latency_snapshot_windowed_delta():
    """Per-scenario readings must delta against a baseline token —
    the histograms are process-lifetime cumulative, and a bench phase
    must not inherit the previous phase's observations."""
    from istio_tpu.runtime import monitor

    monitor.observe_stage("tensorize", 0.010)      # pre-window noise
    base = monitor.stage_baseline()
    monitor.observe_stage("tensorize", 0.020)
    monitor.observe_stage("device_step", 0.040)
    monitor.observe_check_e2e(0.050)
    snap = monitor.latency_snapshot(since=base)
    assert snap["stages"]["tensorize"]["count"] == 1
    assert abs(snap["stages"]["tensorize"]["sum_ms"] - 20.0) < 1e-6
    assert snap["stages"]["device_step"]["count"] == 1
    assert snap["e2e_count"] == 1
    # windowed quantile comes from DELTA bucket counts: the 10ms
    # pre-window observation must not drag p50 down
    assert snap["stages"]["tensorize"]["p50_ms"] >= 20.0
    # unwindowed reading still sees everything
    full = monitor.latency_snapshot()
    assert full["stages"]["tensorize"]["count"] >= 2


def test_rulestats_families_zero_series_before_first_drain():
    """The rule-telemetry counter families (runtime/rulestats.py) must
    expose a zero series BEFORE the first drain — a dashboard has to
    distinguish 'no rule ever fired' from 'telemetry missing'. Private
    registry: the module-level families may already carry traffic from
    other tests."""
    from istio_tpu.runtime import rulestats

    reg = Registry()
    rulestats.register_families(reg)
    samples = _parse(reg.expose_text())
    for fam in ("mixer_rule_check_hits_total",
                "mixer_rule_check_denies_total",
                "mixer_rule_check_errors_total",
                "mixer_rulestats_drains_total"):
        assert samples.get(fam) == [({}, 0.0)], fam
    # the drain-wall histogram emits its zero ladder too
    lint_histograms(reg.expose_text(),
                    expect={"mixer_rulestats_drain_seconds"})


def test_rulestats_families_monotone_across_drains():
    """Per-rule counters are cumulative: two successive drains with
    activity in between must only ever increase each labeled series
    (prometheus counter semantics)."""
    from istio_tpu.runtime import rulestats

    reg = Registry()
    fams = rulestats.register_families(reg)
    agg = rulestats.RuleStatsAggregator(metrics=fams)

    class _Rule:
        def __init__(self, name):
            self.name, self.namespace = name, "ns1"

    class _Tele:
        """Scripted telemetry: each drain yields one hit/deny for
        rule 0 in slot 0."""
        def __init__(self):
            self.generation = 0

        def drain(self):
            self.generation += 1
            return {"generation": self.generation,
                    "hit": np.array([[2, 0]]),
                    "deny": np.array([[1, 0]]),
                    "err": np.array([1, 0]),
                    "exemplars": {}, "exemplars_seen": {},
                    "wall_s": 0.001}

    class _Plan:
        telemetry = _Tele()

    class _Snap:
        rules = [_Rule("r0"), _Rule("r1")]
        revision = 1

        class ruleset:
            ns_ids = {"": 0}

    class _Dispatcher:
        snapshot = _Snap()
        fused = _Plan()

    # attach() drains once (old plan = none), then two live drains
    agg.attach(_Dispatcher())
    readings = []
    for _ in range(2):
        agg.drain()
        samples = _parse(reg.expose_text())
        hits = {tuple(sorted(lb.items())): v for lb, v in
                samples["mixer_rule_check_hits_total"]}
        readings.append(hits.get((("rule", "ns1/r0"),), 0.0))
    assert readings[0] == 2.0 and readings[1] == 4.0, readings
    samples = _parse(reg.expose_text())
    denies = {tuple(sorted(lb.items())): v for lb, v in
              samples["mixer_rule_check_denies_total"]}
    assert denies[(("rule", "ns1/r0"),)] == 2.0
    drains = dict((tuple(sorted(lb.items())), v) for lb, v in
                  samples["mixer_rulestats_drains_total"])
    assert drains[()] >= 2.0


def test_sliding_window_quantiles():
    w = SlidingWindow(100)
    assert w.quantile(0.99) == 0.0
    for i in range(1, 101):
        w.observe(i / 1000.0)
    p50, p99 = w.quantiles((0.5, 0.99))
    assert 0.045 <= p50 <= 0.055
    assert 0.095 <= p99 <= 0.100
    # window slides: old observations age out
    for _ in range(100):
        w.observe(1.0)
    assert w.quantile(0.5) == 1.0
    assert w.total == 200
    w.reset()
    assert len(w) == 0 and w.quantile(0.5) == 0.0


def test_audit_families_zero_shaped_before_first_evaluation():
    """The mesh-audit families (runtime/audit.py) are pre-shaped at
    import: every invariant x status series of mixer_audit_checks,
    every invariant of mixer_audit_violations, every fault kind of
    the explainability counters — all present in the prometheus
    exposition BEFORE the first evaluation, so a dashboard can tell
    'auditor never ran' from 'scrape broken'. The gauges boot to
    their healthy values (1.0), never unset."""
    import prometheus_client

    from istio_tpu.runtime import monitor

    text = prometheus_client.generate_latest(
        monitor.REGISTRY).decode()
    for inv in monitor.AUDIT_INVARIANTS:
        assert f'mixer_audit_violations_total{{invariant="{inv}"}} ' \
            in text, inv
        for st in monitor.AUDIT_STATUSES:
            assert (f'mixer_audit_checks_total{{invariant="{inv}",'
                    f'status="{st}"}} ') in text, (inv, st)
    for kind in monitor.FAULT_KINDS:
        assert ('mixer_fault_explainability_injections_total'
                f'{{kind="{kind}"}} ') in text, kind
        assert ('mixer_fault_explainability_matched_total'
                f'{{kind="{kind}"}} ') in text, kind
    # the gauges carry their boot values, not absence
    assert "mixer_audit_healthy " in text
    assert "mixer_fault_explainability_rate " in text
    assert "mixer_audit_evaluations_total " in text
    # a registry that has seen NO audit activity in this process
    # would expose all-zero counters; with sibling suites running
    # first we can only pin shape — but healthy/explainability must
    # never read below their floor absent a real violation
    counters = monitor.audit_counters()
    assert set(counters["checks"]) == set(monitor.AUDIT_INVARIANTS)
    assert 0.0 <= counters["explainability_rate"] <= 1.0
