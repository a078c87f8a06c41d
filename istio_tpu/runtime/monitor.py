"""Runtime self-metrics (reference: mixer/pkg/runtime/monitor.go:34-88
prometheus counters/histograms for resolve + dispatch).

Two registries live here by design: the prometheus_client REGISTRY
below (the reference's promhttp role) and the homegrown
`utils/metrics.py` default_registry, which carries the serving-path
STAGE decomposition added for the <1ms-p99 north star — per-batch
stage histograms (queue_wait / tensorize / h2d / device_step / fold /
respond), a per-request end-to-end histogram, and a sliding-window
live p50/p95/p99 tracker with an SLO gauge (`check_p99_under_target`)
against the 1ms target. The introspect server
(istio_tpu/introspect/) merges both into one /metrics exposition."""
from __future__ import annotations

import collections
import contextlib
import gc
import logging
import threading
import time
from typing import Callable, Mapping

import prometheus_client

from istio_tpu.utils import metrics as hostmetrics
from istio_tpu.utils import tracing

log = logging.getLogger("istio_tpu.runtime.monitor")

REGISTRY = prometheus_client.CollectorRegistry()

RESOLVE_COUNT = prometheus_client.Counter(
    "mixer_runtime_resolve_count", "resolution batches", registry=REGISTRY)
RESOLVE_DURATION = prometheus_client.Histogram(
    "mixer_runtime_resolve_duration_s", "resolution latency",
    registry=REGISTRY)
RESOLVE_ERRORS = prometheus_client.Counter(
    "mixer_runtime_resolve_errors", "rule predicates that errored",
    registry=REGISTRY)
DISPATCH_COUNT = prometheus_client.Counter(
    "mixer_runtime_dispatch_count", "adapter dispatches",
    registry=REGISTRY)
DISPATCH_DURATION = prometheus_client.Histogram(
    "mixer_runtime_dispatch_duration_s", "adapter dispatch latency",
    registry=REGISTRY)
DISPATCH_ERRORS = prometheus_client.Counter(
    "mixer_runtime_dispatch_errors", "adapter/instance failures",
    registry=REGISTRY)
CONFIG_GENERATION = prometheus_client.Gauge(
    "mixer_runtime_config_generation", "active snapshot revision",
    registry=REGISTRY)
CHECK_BATCH_SIZE = prometheus_client.Histogram(
    "mixer_runtime_check_batch_size", "coalesced check batch sizes",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048),
    registry=REGISTRY)
REPORT_BATCH_SIZE = prometheus_client.Histogram(
    "mixer_runtime_report_batch_size",
    "coalesced report record batch sizes (records from concurrent "
    "Report RPCs share one packed device trip)",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048),
    registry=REGISTRY)
# gRPC serving-path counters (grpcServer.go's monitoring role): a
# failed perf run must be diagnosable from these alone — how many
# requests were decoded vs answered, and how batch formation went.
CHECK_REQUESTS = prometheus_client.Counter(
    "mixer_grpc_check_requests", "Check RPCs decoded", registry=REGISTRY)
CHECK_RESPONSES = prometheus_client.Counter(
    "mixer_grpc_check_responses", "Check responses sent",
    registry=REGISTRY)

# -- the native front's taken batches (api/take.TakenRows): a row is a
# column entry of the pump's buffer until something on the host asks
# for its bag. Beside CHECK_REQUESTS (the rows the front took) it says
# how often that happens.
FRONT_BAGS_MATERIALISED = prometheus_client.Counter(
    "mixer_front_bags_materialised_total",
    "rows of taken batches made into a bag object (asked for by a host "
    "action, a quota, the host oracle, the per-row response, a tap)",
    registry=REGISTRY)


def front_bag_counters() -> dict:
    """Bags made and Check rows decoded, as one JSON-able dict."""
    return {"materialised": int(FRONT_BAGS_MATERIALISED._value.get()),
            "rows": int(CHECK_REQUESTS._value.get())}


# -- overload-resilience counters (runtime/resilience.py + batcher
# admission control). Per-REQUEST counts except batch_failures (per
# batch); label series are pre-touched below so every reason exposes
# at zero from the first scrape (a dashboard must distinguish "never
# shed" from "counter missing").
CHECK_SHED_REASONS = ("queue_full", "brownout", "batcher_dead",
                      "draining")
CHECK_FALLBACK_REASONS = ("breaker_open", "device_error", "fail_open")
CHECK_SHED = prometheus_client.Counter(
    "mixer_check_shed_total",
    "check requests shed by admission control (RESOURCE_EXHAUSTED / "
    "UNAVAILABLE), by reason", ["reason"], registry=REGISTRY)
CHECK_DEADLINE_EXPIRED = prometheus_client.Counter(
    "mixer_check_deadline_expired_total",
    "check requests rejected DEADLINE_EXCEEDED before tensorize",
    registry=REGISTRY)
CHECK_FALLBACK = prometheus_client.Counter(
    "mixer_check_fallback_total",
    "check requests answered off the device path (CPU oracle "
    "fallback, or fail-open OK), by reason", ["reason"],
    registry=REGISTRY)
CHECK_BATCH_FAILURES = prometheus_client.Counter(
    "mixer_check_batch_failures_total",
    "check batches that failed outright (excluded from the stage "
    "decomposition by design — this counter is their only trace)",
    registry=REGISTRY)
CHECK_CANCELLED_SHED = prometheus_client.Counter(
    "mixer_check_cancelled_shed_total",
    "check rows dropped at batch build because the caller already "
    "cancelled (aio client disconnect)", registry=REGISTRY)
CHECK_DEVICE_RETRIES = prometheus_client.Counter(
    "mixer_check_device_retries_total",
    "device check steps retried after a transient failure",
    registry=REGISTRY)
BREAKER_STATE = prometheus_client.Gauge(
    "mixer_check_breaker_state",
    "device circuit breaker state: 0=closed 1=half_open 2=open",
    registry=REGISTRY)
BREAKER_TRANSITIONS = prometheus_client.Counter(
    "mixer_check_breaker_transitions_total",
    "device circuit breaker state transitions, by target state",
    ["to"], registry=REGISTRY)
for _r in CHECK_SHED_REASONS:
    CHECK_SHED.labels(reason=_r)
for _r in CHECK_FALLBACK_REASONS:
    CHECK_FALLBACK.labels(reason=_r)
for _s in ("closed", "half_open", "open"):
    BREAKER_TRANSITIONS.labels(to=_s)


def resilience_counters() -> dict:
    """Resilience counter snapshot as one JSON-able dict — read by
    /debug/resilience, the chaos smoke and the benchmark's `correct`
    (its fallback counters must not move inside a window)."""
    shed = {r: int(CHECK_SHED.labels(reason=r)._value.get())
            for r in CHECK_SHED_REASONS}
    fb = {r: int(CHECK_FALLBACK.labels(reason=r)._value.get())
          for r in CHECK_FALLBACK_REASONS}
    return {
        "shed": shed,
        "shed_total": sum(shed.values()),
        "expired_total": int(CHECK_DEADLINE_EXPIRED._value.get()),
        "fallback": fb,
        "fallback_total": sum(fb.values()),
        "batch_failures_total": int(CHECK_BATCH_FAILURES._value.get()),
        "cancelled_shed_total": int(CHECK_CANCELLED_SHED._value.get()),
        "device_retries_total": int(CHECK_DEVICE_RETRIES._value.get()),
        "breaker_state": int(BREAKER_STATE._value.get()),
    }


# -- what decided each served verdict (Dispatcher._fold_respond, once a
# batch, from the pulled numpy planes). `by`: the section of the device
# step whose rule gave the row its status (deny / list / rbac; ok when
# none did), or host when the device left the row OK and a
# host-overlay action was active on it, so host adapters had the word.
CHECK_DECIDED_BY = ("ok", "deny", "list", "rbac", "host")
CHECK_DECIDED = prometheus_client.Counter(
    "mixer_check_decided_total",
    "served check rows by the section that decided the verdict",
    ["by"], registry=REGISTRY)
FOLD_SIGNATURE_CLASSES = prometheus_client.Counter(
    "mixer_fold_signature_classes_total",
    "distinct referenced/presence signatures over the served batches "
    "(/ the count of span fold.signature = mean classes a batch)",
    registry=REGISTRY)
for _r in CHECK_DECIDED_BY:
    CHECK_DECIDED.labels(by=_r)
# the respond stage's verdict classes (Dispatcher._fold_respond): the
# distinct CheckResponse objects a batch built, and its rows by the way
# each got its object — `classed`: shared with another row of its
# class; `row`: an object of its own (a class of one, a row under a
# host action, a batch under RESPOND_CLASS_MIN_ROWS).
RESPOND_CLASSES = prometheus_client.Counter(
    "mixer_respond_classes_total",
    "distinct CheckResponse objects built over the served batches "
    "(/ the count of stage respond = mean verdict classes a batch)",
    registry=REGISTRY)
RESPOND_ROW_PATHS = ("classed", "row")
RESPOND_ROWS = prometheus_client.Counter(
    "mixer_respond_rows_total",
    "served check rows by how the respond stage built their response",
    ["path"], registry=REGISTRY)
for _r in RESPOND_ROW_PATHS:
    RESPOND_ROWS.labels(path=_r)


def note_check_decided(rows_by_section) -> None:
    """One batch's rows per CHECK_DECIDED_BY entry, in that order."""
    for by, rows in zip(CHECK_DECIDED_BY, rows_by_section):
        if rows:
            CHECK_DECIDED.labels(by=by).inc(int(rows))


def note_respond_classes(classes: int, classed: int, row: int) -> None:
    """One batch: objects built, rows that share one, rows with their
    own."""
    RESPOND_CLASSES.inc(classes)
    if classed:
        RESPOND_ROWS.labels(path="classed").inc(classed)
    if row:
        RESPOND_ROWS.labels(path="row").inc(row)


def respond_class_counters() -> dict:
    """The class sum and {path: rows}, as one JSON-able dict."""
    return {
        "classes_total": int(RESPOND_CLASSES._value.get()),
        "rows": {path: int(RESPOND_ROWS.labels(path=path)._value.get())
                 for path in RESPOND_ROW_PATHS},
    }


def check_decided_counters() -> dict:
    """{by: rows} and the signature-class sum, as one JSON-able dict."""
    return {
        "decided": {by: int(CHECK_DECIDED.labels(by=by)._value.get())
                    for by in CHECK_DECIDED_BY},
        "signature_classes_total":
            int(FOLD_SIGNATURE_CLASSES._value.get()),
    }


# -- device programs launched for served Check batches. `path`: the
# launch site (FusedPlan.packed_check -> check, packed_check_instep ->
# instep). Over the count of span dispatch.step, which every served
# batch observes once at either site, it is the programs a batch pays
# to launch: 1 where the step, the rule-telemetry fold and the packer
# are one jit, 4 where they are launched apart (in-step quota, a
# mesh). Prewarm dummies and Report traffic count nothing.
DEVICE_PROGRAM_PATHS = ("check", "instep")
DEVICE_PROGRAMS = prometheus_client.Counter(
    "mixer_device_programs_total",
    "device programs launched for served check batches, by launch site",
    ["path"], registry=REGISTRY)
for _r in DEVICE_PROGRAM_PATHS:
    DEVICE_PROGRAMS.labels(path=_r)


def note_device_programs(path: str, n: int) -> None:
    DEVICE_PROGRAMS.labels(path=path).inc(n)


def device_program_counters() -> dict:
    """{path: programs launched} as one JSON-able dict."""
    return {path: int(DEVICE_PROGRAMS.labels(path=path)._value.get())
            for path in DEVICE_PROGRAM_PATHS}


# -- the length split of the fused Check path (Dispatcher.
# _split_by_length). `width`: the byte-plane width a served row's
# program ran at, a narrow tier or the wide plane, added once a
# batch; `subject`: the byte slot that saturated the wide plane on a
# row the host then decided (Dispatcher._decide_on_host), the row's
# first where several did.
CHECK_ROWS_BY_WIDTH = hostmetrics.default_registry.counter(
    "mixer_check_rows_by_width_total",
    "served check rows by the byte-plane width of the program that "
    "served them (label: width)")
CHECK_UNDECIDED_ROWS = hostmetrics.default_registry.counter(
    "mixer_check_undecided_rows_total",
    "served check rows the device left undecided (a subject past the "
    "widest byte plane under a rule that reads it) and the host "
    "decided (label: subject)")


# zero-series before the first batch: the default layout's widest
# narrow tier and wide plane (compiler/layout.py), the request line
for _w in ("128", "2048"):
    CHECK_ROWS_BY_WIDTH.inc(0, width=_w)
CHECK_UNDECIDED_ROWS.inc(0, subject="request.path")


def note_rows_by_width(rows_by_width: Mapping) -> None:
    """One batch: {byte-plane width: rows served at it}."""
    for width, rows in rows_by_width.items():
        if rows:
            CHECK_ROWS_BY_WIDTH.inc(int(rows), width=str(width))


def note_undecided_rows(byte_slots: list, firsts: list) -> None:
    """One batch's host-decided rows: `firsts[i]` indexes, in the
    layout's `byte_slots`, the first subject of row i that fills the
    wide plane."""
    for at in firsts:
        src = byte_slots[at]
        CHECK_UNDECIDED_ROWS.inc(subject=src if isinstance(src, str)
                                 else f"{src[0]}[{src[1]}]")


def length_split_counters() -> dict:
    """{"rows_by_width": {width: rows}, "undecided": {subject: rows}}
    as one JSON-able dict."""
    return {
        "rows_by_width": {labels["width"]: int(
            CHECK_ROWS_BY_WIDTH.value(**labels))
            for labels in CHECK_ROWS_BY_WIDTH.label_sets()},
        "undecided": {labels["subject"]: int(
            CHECK_UNDECIDED_ROWS.value(**labels))
            for labels in CHECK_UNDECIDED_ROWS.label_sets()},
    }


# -- the attribute-generation phase (runtime/apa.py): Check rows whose
# APA ran in the step (`step`, counted a served batch) or on the host
# (`host`: Dispatcher.preprocess_batch, a bag a row), OK quota rows
# of a batch that carries the step's pod rows, keyed from them or built
# on a bag bound from them (`quota`: the native front's quota group and
# RuntimeServer.quota_bags), and the device table of the APA
# the step holds, set at plan build (0 for a plan whose APA is the
# host's or that has none).
APA_ROW_PATHS = ("step", "host", "quota")
APA_ROWS = prometheus_client.Counter(
    "mixer_apa_rows_total",
    "Check rows whose attribute generation ran in the step or on the "
    "host, and quota rows bound from the step's pod rows", ["path"],
    registry=REGISTRY)
for _r in APA_ROW_PATHS:
    APA_ROWS.labels(path=_r)
APA_TABLE_BYTES = hostmetrics.default_registry.gauge(
    "mixer_apa_table_bytes",
    "resident device bytes of the step's APA table (a pod a row)")
APA_TABLE_ROWS = hostmetrics.default_registry.gauge(
    "mixer_apa_table_rows", "pods in the step's APA table")


def note_apa_rows(path: str, n: int) -> None:
    APA_ROWS.labels(path=path).inc(n)


def note_apa_table(apa) -> None:
    """`apa`: the plan's runtime/apa.ApaStep, or None."""
    APA_TABLE_BYTES.set(apa.table_bytes if apa is not None else 0)
    APA_TABLE_ROWS.set(apa.rows if apa is not None else 0)


def apa_counters() -> dict:
    """{"rows": {path: rows}, "table_bytes", "table_rows"} as one
    JSON-able dict."""
    return {"rows": {path: int(APA_ROWS.labels(path=path)._value.get())
                     for path in APA_ROW_PATHS},
            "table_bytes": int(APA_TABLE_BYTES.value()),
            "table_rows": int(APA_TABLE_ROWS.value())}


# -- the device quota pools (runtime/device_quota.py): the worker's
# flushes, from the take of a batch of pending rows to the last of its
# completions handed on (staging, the device trip, grants and the
# answers' framing), and the rows they answered; and the native
# front's quota rows by how their response was built: `class`, a
# response a class of equal bytes (a verdict class and the same
# grants), or `row`, built from the row's own bag.
QUOTA_FLUSH_SECONDS = hostmetrics.default_registry.histogram(
    "mixer_quota_flush_seconds",
    "wall of a quota pool worker's flush, take to last completion")
QUOTA_FLUSH_ROWS = hostmetrics.default_registry.counter(
    "mixer_quota_flush_rows_total", "quota rows the pool flushes answered")
QUOTA_FLUSH_ROWS.inc(0)
QUOTA_ANSWER_PATHS = ("class", "row")
QUOTA_ROWS_ANSWERED = prometheus_client.Counter(
    "mixer_quota_rows_answered_total",
    "native front quota rows answered, by how their response was built",
    ["path"], registry=REGISTRY)
for _r in QUOTA_ANSWER_PATHS:
    QUOTA_ROWS_ANSWERED.labels(path=_r)


def note_quota_rows_answered(classed: int, row: int) -> None:
    if classed:
        QUOTA_ROWS_ANSWERED.labels(path="class").inc(classed)
    if row:
        QUOTA_ROWS_ANSWERED.labels(path="row").inc(row)


# the native front's asks of a device pool by where their memquota key
# came from (runtime/quota_keys.py): `memo`, the plan's memo over the
# batch's interned ids and the stamp's pod rows; `built`, a miss there,
# the instance built on the row's bag and its key stored; `row`, the
# per-row build (a plan, an instance or a row whose ids do not decide
# the key, or a build error)
QUOTA_KEY_PATHS = ("memo", "built", "row")
QUOTA_KEYS = prometheus_client.Counter(
    "mixer_quota_keys_total",
    "native front quota asks, by where their memquota key came from",
    ["path"], registry=REGISTRY)
for _r in QUOTA_KEY_PATHS:
    QUOTA_KEYS.labels(path=_r)


def note_quota_keys(memo: int, built: int, row: int) -> None:
    """A batch's asks by path: one increment a path that had any."""
    for path, n in zip(QUOTA_KEY_PATHS, (memo, built, row)):
        if n:
            QUOTA_KEYS.labels(path=path).inc(n)


def quota_key_counters() -> dict:
    """{path: asks} as one JSON-able dict."""
    return {path: int(QUOTA_KEYS.labels(path=path)._value.get())
            for path in QUOTA_KEY_PATHS}


def quota_counters() -> dict:
    """{"flush_s", "flushes", "flush_rows", "answered": {path: rows}}
    as one JSON-able dict."""
    _, flush_s, flushes = QUOTA_FLUSH_SECONDS.state()
    return {"flush_s": flush_s, "flushes": flushes,
            "flush_rows": int(QUOTA_FLUSH_ROWS.value()),
            "answered": {
                path: int(QUOTA_ROWS_ANSWERED.labels(path=path)
                          ._value.get())
                for path in QUOTA_ANSWER_PATHS}}


# -- the resident DFA banks of the plan in force, set at plan build
# (runtime/fused.py) from the compiled ruleset's geometry: what the
# snapshot's constant-pattern regexes cost on the device and how the
# compiler chose to scan them (tier: onehot / onehot-blocked /
# candidates / gather, ops/regex_dfa.pack_dfas_tiered). `subject` is
# the scanned expression (request.path, a header probe).
DFA_BANK_BYTES = hostmetrics.default_registry.gauge(
    "mixer_dfa_bank_bytes",
    "resident device bytes of one subject's DFA bank (label: subject)")
DFA_BANK_AUTOMATA = hostmetrics.default_registry.gauge(
    "mixer_dfa_bank_automata",
    "automata in one subject's DFA bank (labels: subject, tier)")
DFA_CANDIDATES_MAX = hostmetrics.default_registry.gauge(
    "mixer_dfa_candidates_max",
    "automata one row is scanned against: the bank's size, or under "
    "tier=candidates the most any guard value holds plus the automata "
    "no value guards (label: subject)")


def note_dfa_banks(banks) -> None:
    """`banks`: RuleSetProgram.geometry["dfa_banks"] of the plan being
    built; a subject whose bank is split (its guarded automata under
    tier=candidates, the others beside them) reads the sum. Series of
    a bank the new plan no longer has read 0."""
    gauges = (DFA_BANK_BYTES, DFA_BANK_AUTOMATA, DFA_CANDIDATES_MAX)
    for gauge in gauges:
        for labels in gauge.label_sets():
            gauge.set(0, **labels)
    for b in banks:
        by = {"subject": b["subject"]}
        DFA_BANK_BYTES.set(DFA_BANK_BYTES.value(**by) + b["bytes"], **by)
        DFA_BANK_AUTOMATA.set(b["automata"], tier=b["tier"], **by)
        DFA_CANDIDATES_MAX.set(
            DFA_CANDIDATES_MAX.value(**by) + b["candidates"], **by)


# -- adapter-executor plane (runtime/executor.py) --------------------
#
# Conservation invariant (the report plane's doctrine applied to host
# actions): every host adapter call SUBMITTED to the executor resolves
# with EXACTLY one outcome — ok (adapter result used), error (adapter
# exception → safeDispatch INTERNAL), shed (bulkhead queue full /
# closed lane), expired (request deadline gone before the wait),
# overrun (still running at the deadline → fail-policy verdict),
# breaker_open (lane breaker short-circuit) — so
# submitted == sum(outcomes) holds at quiescence. A worker finishing
# an action the fold already abandoned counts late_{ok,error}
# SEPARATELY: late results are accounting, never verdicts.
HOST_ACTION_OUTCOMES = ("ok", "error", "shed", "expired", "overrun",
                        "breaker_open")
HOST_ACTIONS_SUBMITTED = hostmetrics.default_registry.counter(
    "mixer_host_actions_submitted_total",
    "host adapter calls submitted to the executor plane, by handler")
HOST_ACTIONS = hostmetrics.default_registry.counter(
    "mixer_host_actions_total",
    "host adapter calls resolved, by handler and outcome (see "
    "runtime/monitor.py HOST_ACTION_OUTCOMES)")
HOST_ACTION_SECONDS = hostmetrics.default_registry.histogram(
    "mixer_host_action_seconds",
    "wall seconds of completed host adapter calls, by handler")
HOST_ACTION_LATE = hostmetrics.default_registry.counter(
    "mixer_host_action_late_total",
    "host adapter calls completing AFTER their fold abandoned them "
    "(outcome already counted overrun/expired), by handler and result")
HOST_ACTION_RETRIES = hostmetrics.default_registry.counter(
    "mixer_host_action_retries_total",
    "host adapter calls retried after a transient exception")
HOST_ACTIONS_SUBMITTED.inc(0)   # zero-series before the first action
HOST_ACTIONS.inc(0)
HOST_ACTION_LATE.inc(0)
HOST_ACTION_RETRIES.inc(0)

# provider refresh (the executor's maintenance lane driving
# list_adapter's TTL loop): attempts vs failures + per-provider age
# in /debug/executor — a stale list must be visible, not silent
LIST_REFRESH_TOTAL = prometheus_client.Counter(
    "mixer_list_provider_refresh_total",
    "list provider refresh attempts (maintenance lane)",
    registry=REGISTRY)
LIST_REFRESH_FAILURES = prometheus_client.Counter(
    "mixer_list_provider_refresh_failures",
    "list provider refresh attempts that failed (the last good list "
    "keeps serving)", registry=REGISTRY)


def note_host_action_submitted(handler: str) -> None:
    HOST_ACTIONS_SUBMITTED.inc(1, handler=handler)


def note_host_action(handler: str, outcome: str,
                     seconds: float | None = None) -> None:
    """One resolved host action (runtime/executor.AdapterExecutor.
    resolve — the single accounting home)."""
    HOST_ACTIONS.inc(1, handler=handler, outcome=outcome)
    if seconds is not None:
        HOST_ACTION_SECONDS.observe(seconds, handler=handler)


def note_host_action_late(handler: str, result: str) -> None:
    HOST_ACTION_LATE.inc(1, handler=handler, result=result)


def note_host_action_retry(handler: str) -> None:
    HOST_ACTION_RETRIES.inc(1, handler=handler)


def host_action_counters() -> dict:
    """Executor-plane counter snapshot as one JSON-able dict — read by
    /debug/executor and the executor smoke. `exact` is the
    conservation check (True whenever nothing is in flight)."""
    by_handler: dict[str, dict] = {}
    submitted_total = 0
    with HOST_ACTIONS_SUBMITTED._lock:
        sub = dict(HOST_ACTIONS_SUBMITTED._values)
    for labels, v in sub.items():
        h = dict(labels).get("handler")
        if h is None:
            continue
        by_handler.setdefault(h, {"submitted": 0, "outcomes": {}})
        by_handler[h]["submitted"] += int(v)
        submitted_total += int(v)
    resolved_total = 0
    outcome_totals = {o: 0 for o in HOST_ACTION_OUTCOMES}
    with HOST_ACTIONS._lock:
        res = dict(HOST_ACTIONS._values)
    for labels, v in res.items():
        lab = dict(labels)
        h, o = lab.get("handler"), lab.get("outcome")
        if h is None or o is None:
            continue
        by_handler.setdefault(h, {"submitted": 0, "outcomes": {}})
        by_handler[h]["outcomes"][o] = \
            by_handler[h]["outcomes"].get(o, 0) + int(v)
        outcome_totals[o] = outcome_totals.get(o, 0) + int(v)
        resolved_total += int(v)
    late = {"ok": 0, "error": 0}
    with HOST_ACTION_LATE._lock:
        for labels, v in dict(HOST_ACTION_LATE._values).items():
            r = dict(labels).get("result")
            if r in late:
                late[r] += int(v)
    with HOST_ACTION_RETRIES._lock:
        retries = sum(int(v) for labels, v in
                      dict(HOST_ACTION_RETRIES._values).items()
                      if dict(labels).get("handler") is not None)
    return {
        "submitted": submitted_total,
        "resolved": resolved_total,
        "in_flight": submitted_total - resolved_total,
        "outcomes": outcome_totals,
        "late": late,
        "retries": retries,
        "by_handler": by_handler,
        "exact": submitted_total == resolved_total,
        "refresh_total": int(LIST_REFRESH_TOTAL._value.get()),
        "refresh_failures": int(LIST_REFRESH_FAILURES._value.get()),
    }


# -- tail-latency forensics plane (runtime/forensics.py) --------------
#
# Two bounded rings back the forensics surfaces: the flight recorder's
# slow-request exemplar ring (/debug/slow) and the mesh event timeline
# (/debug/events). Overflow on either is bounded AND typed — the
# dropped family below is zero-shaped per ring before the first drop
# (a dashboard must distinguish "never dropped" from "counter
# missing"), exactly the promtext doctrine the shed counters follow.
FORENSICS_RINGS = ("slow", "events")
FORENSICS_DROPPED = prometheus_client.Counter(
    "mixer_forensics_dropped_total",
    "forensics ring entries evicted by overflow, by ring "
    "(slow = flight-recorder exemplars, events = mesh event "
    "timeline)", ["ring"], registry=REGISTRY)
FORENSICS_SLOW = prometheus_client.Counter(
    "mixer_forensics_slow_exemplars_total",
    "slow-request exemplars captured by the flight recorder "
    "(one per over-threshold batch)", registry=REGISTRY)
FORENSICS_EVENTS = prometheus_client.Counter(
    "mixer_forensics_events_total",
    "control-plane events recorded on the mesh event timeline",
    registry=REGISTRY)
for _r in FORENSICS_RINGS:
    FORENSICS_DROPPED.labels(ring=_r)


def note_forensics_drop(ring: str) -> None:
    if ring not in FORENSICS_RINGS:
        ring = "slow"
    FORENSICS_DROPPED.labels(ring=ring).inc()


def forensics_counters() -> dict:
    """Forensics counter snapshot as one JSON-able dict — read by
    /debug/slow and the forensics smoke."""
    return {
        "slow_captured": int(FORENSICS_SLOW._value.get()),
        "events_recorded": int(FORENSICS_EVENTS._value.get()),
        "dropped": {r: int(FORENSICS_DROPPED.labels(
            ring=r)._value.get()) for r in FORENSICS_RINGS},
    }


# -- secure plane: workload identity + mTLS admission ----------------
#
# Lifecycle counters for the WorkloadIdentity rotation loop
# (istio_tpu/secure/identity.py) and the mTLS admission boundary on
# the serving fronts. Zero-shaped per the promtext doctrine: every
# (event, outcome) series exposes at 0 from the first scrape.
IDENTITY_EVENTS_KINDS = ("issue", "rotate", "expiry")
IDENTITY_OUTCOMES = ("ok", "failed")
IDENTITY_EVENTS = prometheus_client.Counter(
    "mixer_identity_events_total",
    "workload-identity lifecycle transitions (issue = first obtain, "
    "rotate = renewal, expiry = cert died before renewal), by "
    "outcome", ["event", "outcome"], registry=REGISTRY)
IDENTITY_UNAUTHENTICATED = prometheus_client.Counter(
    "mixer_identity_unauthenticated_total",
    "requests rejected typed UNAUTHENTICATED at strict-mTLS "
    "admission (no verified peer SPIFFE identity)",
    registry=REGISTRY)
IDENTITY_AUTHENTICATED = prometheus_client.Counter(
    "mixer_identity_authenticated_checks_total",
    "check admissions whose attribute bag carried a verified peer "
    "SPIFFE identity (source.user from the client cert)",
    registry=REGISTRY)
for _e in IDENTITY_EVENTS_KINDS:
    for _o in IDENTITY_OUTCOMES:
        IDENTITY_EVENTS.labels(event=_e, outcome=_o)


def note_identity(event: str, outcome: str) -> None:
    if event not in IDENTITY_EVENTS_KINDS:
        event = "issue"
    if outcome not in IDENTITY_OUTCOMES:
        outcome = "failed"
    IDENTITY_EVENTS.labels(event=event, outcome=outcome).inc()


def identity_counters() -> dict:
    """Secure-plane counter snapshot — /debug/identity and the mtls
    smoke read this."""
    events = {e: {o: int(IDENTITY_EVENTS.labels(
        event=e, outcome=o)._value.get())
        for o in IDENTITY_OUTCOMES} for e in IDENTITY_EVENTS_KINDS}
    return {
        "events": events,
        "rotations_ok": events["rotate"]["ok"],
        "unauthenticated_total":
            int(IDENTITY_UNAUTHENTICATED._value.get()),
        "authenticated_checks_total":
            int(IDENTITY_AUTHENTICATED._value.get()),
    }


# -- end-to-end Check() latency decomposition ------------------------
#
# Stage semantics (one observation per BATCH per stage; e2e is one
# observation per REQUEST, so sum-of-stage-sums <= sum-of-e2e holds
# whenever batches carry >= 1 request). Every stage is one
# `with monitor.stage(name):` site:
#   queue_wait  — oldest enqueue -> batch start (batcher) or entry ->
#                 dispatch (check_many). The native front has no such
#                 stage: its C++ queue wait is NativeMixerServer.
#                 queue_wait(), its blocked pump the `take_wait` span
#   tensorize   — wire bytes / bags -> AttributeBatch, host side, AND
#                 (native wire path, overlap_h2d) the one explicit
#                 device_put of the byte plane, AND the ns ids; split
#                 by the spans tensorize.decode / .stage_put / .ns_ids;
#                 inside .decode, tensorize.call_wait is the wait for
#                 the tensorizer's one call lock (both pumps decode
#                 through one NativeTensorizer)
#   h2d         — misnamed, kept for its readers: the LAUNCH of the
#                 batch's device program(s) with the implicit transfer
#                 of every jit argument. One program (span
#                 dispatch.step) on FusedPlan.packed_check; step,
#                 rule-telemetry fold and packer launched apart (spans
#                 dispatch.step / .rulestats / .pack) for an in-step
#                 quota batch or under a mesh
#   device_step — the blocking device->host pull of the packed verdict
#                 (waits out the programs dispatched in `h2d`, then
#                 the D2H copy; ~0.9 ms of sync on a local chip)
#   fold        — packed-plane decode: overlay bits, host-action
#                 submits, referenced / presence signature dedup
#                 (the span fold.signature)
#   respond     — one CheckResponse a verdict class (grants included)
CHECK_STAGES = ("queue_wait", "tensorize", "h2d", "device_step",
                "fold", "respond")
CHECK_P99_TARGET_MS = 1.0   # BASELINE north star: <1ms p99 at 10k rules

CHECK_STAGE_SECONDS = hostmetrics.default_registry.histogram(
    "mixer_check_stage_seconds",
    "per-batch serving stage latency (label: stage)")
CHECK_E2E_SECONDS = hostmetrics.default_registry.histogram(
    "mixer_check_e2e_seconds",
    "per-request served check latency, enqueue to response")
CHECK_WINDOW = hostmetrics.SlidingWindow(4096)
CHECK_P50_MS = hostmetrics.default_registry.gauge(
    "mixer_check_p50_ms", "sliding-window served check p50 (ms)")
CHECK_P95_MS = hostmetrics.default_registry.gauge(
    "mixer_check_p95_ms", "sliding-window served check p95 (ms)")
CHECK_P99_MS = hostmetrics.default_registry.gauge(
    "mixer_check_p99_ms", "sliding-window served check p99 (ms)")
CHECK_SLO_GAUGE = hostmetrics.default_registry.gauge(
    "check_p99_under_target",
    f"1 when the sliding-window check p99 is under the "
    f"{CHECK_P99_TARGET_MS}ms target (vacuously 1 while the window is "
    f"empty — mask alerts on mixer_check_e2e_seconds_count), else 0")


# Everything else on the served path that is timed but is NOT one of
# the six stages lands here (label: span) — the native pump's cycle
# (pump_cycle = take_wait + wire_decode + the stages + serialize +
# send), the sub-spans of `tensorize` and `h2d`, and — only while a
# zipkin reporter is configured (zipkin_on) — the tracer's grouping
# spans (device = h2d + device_step, overlay = fold + respond).
# Surfaced by latency_snapshot()["spans"], never under "stages".
PUMP_SPAN_SECONDS = hostmetrics.default_registry.histogram(
    "mixer_pump_span_seconds",
    "served-path host spans outside the six check stages "
    "(label: span)")
GC_PAUSE_SECONDS = hostmetrics.default_registry.histogram(
    "mixer_gc_pause_seconds",
    "stop-the-world wall of full (generation 2) garbage collections "
    "of the serving process")
GC_YOUNG_SECONDS = hostmetrics.default_registry.histogram(
    "mixer_gc_young_seconds",
    "wall of young (generation 0 and 1) garbage collections of the "
    "serving process")
GC_FROZEN_OBJECTS = hostmetrics.default_registry.gauge(
    "mixer_gc_frozen_objects",
    "objects in the collector's permanent generation after the last "
    "settle_heap at a named site (0: the heap is not frozen)")
HEAP_SETTLES = hostmetrics.default_registry.counter(
    "mixer_heap_settles_total",
    "settle_heap calls that froze the heap (label: at)")
HEAP_SETTLE_SITES = ("init", "start", "publish", "gc")
for _at in HEAP_SETTLE_SITES:   # zero-series before the first settle
    HEAP_SETTLES.inc(0, at=_at)


# forensics stage tap (runtime/forensics.py registers the flight
# recorder's thread-local tape here at import): every check stage
# observation ALSO lands on the open batch tape, so the recorder needs
# no second set of timers on the hot path. None until forensics loads.
_STAGE_TAP = None


def set_stage_tap(fn) -> None:
    global _STAGE_TAP
    _STAGE_TAP = fn


def observe_stage(stage: str, seconds: float) -> None:
    """An already-measured stage wall (the batcher's queue_wait is an
    enqueue -> batch-start difference, not a `with` block). Code that
    runs the stage uses stage() below."""
    CHECK_STAGE_SECONDS.observe(seconds, stage=stage)
    if _STAGE_TAP is not None:
        _STAGE_TAP(stage, seconds)


# -- the one span API of the served Check path -------------------------
#
# `with monitor.stage(name):` / `with monitor.span(name):` does three
# things over ONE interval: (1) observes the wall into a histogram
# (stage -> mixer_check_stage_seconds, span -> mixer_pump_span_seconds;
# always on: a perf_counter pair and one observe); (2) holds a
# jax.profiler.TraceAnnotation "mixer/<name>", so that while a profiler
# session runs (/debug/profile, a benchmark's traced window) the span
# lies on the host plane of the same xplane, on the same clock, as the
# device's `XLA Modules` line — with no session it is a TraceMe that
# checks one flag; (3) feeds what hangs on the timers: the forensics
# stage tap and, only when a reporter is configured, the zipkin tracer
# (names in _ZIPKIN_NAMES: the three spans /debug/traces had, and
# fold.signature, whose `distinct` tag only the tracer carries). A
# block that raises closes its annotation and observes nothing:
# failed batches stay out of the decomposition by design
# (mixer_check_batch_failures_total is their trace).

_ZIPKIN_NAMES = {"tensorize": "serve.tensorize",
                 "device": "serve.device",
                 "overlay": "serve.overlay",
                 "fold.signature": "serve.fold.signature"}
_ANNOTATION_PREFIX = "mixer/"


def _trace_annotation(name: str):
    """First use: bind the profiler's TraceMe class (jax stays a lazy
    import of this module); a rig without it gets a null context."""
    global _trace_annotation
    try:
        from jax.profiler import TraceAnnotation
        _trace_annotation = TraceAnnotation
    except Exception:   # no profiler: spans still time and observe
        _trace_annotation = lambda _name: _OFF   # noqa: E731
    return _trace_annotation(name)


class _Off:
    """The disabled span (`on=False`): one shared, stateless object."""
    __slots__ = ()
    seconds = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def tag(self, **tags) -> None:
        pass


_OFF = _Off()
_SPAN_META: dict = {}


class _PumpLocal(threading.local):
    slot = None     # what a thread that is no pump reads


class _PumpSlot:
    """One pump's open spans, for the pump watch below: `open` is the
    innermost one as (name, t0, the entry it is nested in), None
    between spans. Written by the pump alone, read by the watch."""
    __slots__ = ("pump", "thread", "open")

    def __init__(self, pump: int):
        self.pump = pump
        self.thread = threading.current_thread()
        self.open = None


_PUMP = _PumpLocal()
_PUMP_SLOTS: dict[int, _PumpSlot] = {}      # by thread ident


def pump_enter(pump: int) -> None:
    """The calling thread is pump number `pump` of a native front from
    here to pump_leave(): its spans write their name and start into a
    slot the pump watch reads while they are still open."""
    slot = _PUMP.slot = _PumpSlot(pump)
    _PUMP_SLOTS[slot.thread.ident] = slot


def pump_leave() -> None:
    slot, _PUMP.slot = _PUMP.slot, None
    if slot is not None:
        _PUMP_SLOTS.pop(slot.thread.ident, None)


class _Span:
    __slots__ = ("_meta", "_tags", "_ann", "_t0", "_slot", "_outer",
                 "seconds")

    def __init__(self, meta: tuple, tags: dict):
        self._meta = meta
        self._tags = tags
        self.seconds = 0.0

    def __enter__(self):
        self._ann = _trace_annotation(self._meta[2])
        self._ann.__enter__()
        t0 = self._t0 = time.perf_counter()
        slot = self._slot = _PUMP.slot
        if slot is not None:
            outer = self._outer = slot.open
            slot.open = (self._meta[3], t0, outer)
        return self

    def tag(self, **tags) -> None:
        """Tags the block itself computes (a count known at its end)."""
        self._tags.update(tags)

    def __exit__(self, exc_type, exc, tb) -> bool:
        seconds = self.seconds = time.perf_counter() - self._t0
        if self._slot is not None:
            self._slot.open = self._outer
        self._ann.__exit__(exc_type, exc, tb)
        hist, key, _, name, tap, zipkin = self._meta
        if exc_type is None:
            hist.observe_key(key, seconds)
            if tap and _STAGE_TAP is not None:
                _STAGE_TAP(name, seconds)
        if zipkin is not None:
            tracer = tracing.get_tracer()
            if tracer.reporter is not None:
                if exc_type is not None:
                    self._tags["error"] = str(exc)
                tracer.emit(zipkin, seconds, **self._tags)
        return False


def _span(hist, label: str, name: str, tap: bool, tags: dict) -> _Span:
    meta = _SPAN_META.get((label, name, tap))
    if meta is None:
        meta = _SPAN_META[(label, name, tap)] = (
            hist, ((label, name),), _ANNOTATION_PREFIX + name, name,
            tap, _ZIPKIN_NAMES.get(name))
    return _Span(meta, tags)


def zipkin_on() -> bool:
    """Whether a zipkin reporter is configured: the condition for a
    span that only the tracer reads (serve.device, serve.overlay)."""
    return tracing.get_tracer().reporter is not None


def stage(name: str, on: bool = True, **tags):
    """One of the six CHECK_STAGES, as a context manager (see above).
    `on=False` (prewarm dummies, report/replay callers of shared code)
    times and observes nothing. `tags` ride the zipkin span only."""
    if not on:
        return _OFF
    return _span(CHECK_STAGE_SECONDS, "stage", name, True, tags)


def span(name: str, on: bool = True, tap: bool = False, **tags):
    """A served-path span that is not a stage: histogram
    mixer_pump_span_seconds{span}, latency_snapshot()["spans"].
    `.seconds` holds the wall after the block. `tap`: also mark the
    flight recorder's open batch tape (the `grant` decision)."""
    if not on:
        return _OFF
    return _span(PUMP_SPAN_SECONDS, "span", name, tap, tags)


# -- full garbage collections of the serving process -------------------
#
# A generation-2 collection stops every thread for as long as it walks
# the heap: both pumps and every blocked request wait it out. At a
# 10k-rule snapshot the heap is ~800k objects (0.3 s a walk), almost
# all of them the snapshot, its handlers, the traced step programs the
# jit cache keeps alive and imported modules: nothing that can become
# garbage before the next config publish. settle_heap() puts them in
# the collector's permanent generation, which no collection walks.
#
# Freezing most of it buys nothing: CPython runs a full collection
# once a quarter of the last one's survivors has been promoted, so the
# walks get shorter and as much more frequent, and their share of the
# wall stays what it was (12 % with 580k objects frozen and 207k not:
# PERF.md, PR 30).
# It falls only when the unfrozen survivors are fewer than the rows in
# flight. So besides the named sites the hook settles again after any
# full collection that took RESETTLE_PAUSE_S: what it walked had
# survived, whoever built it (the host oracle's per-rule programs,
# compiled as fallback traffic first touches each rule; caches filled
# by the first requests; the embedding process's own data).
#
# Hook and frozen heap live as long as a RuntimeServer does
# (refcounted: tests run several servers in one process).

_GC_LOCK = threading.Lock()
_GC_USERS = 0
_GC_OPEN: list = []     # [(annotation, t0)] of the collection running
_GC_YOUNG_T0 = 0.0      # start of the young collection running
# the rows in flight alone cost a full collection 2-5 ms; at about two
# collections a second this bounds what is left at ~2 % of the wall
RESETTLE_PAUSE_S = 0.010


def _on_gc(phase: str, info: dict) -> None:
    if info["generation"] != 2:
        # a young collection: its wall and nothing else (some 300 a
        # second in a deep cell). Collections do not nest: one stamp
        global _GC_YOUNG_T0
        if phase == "start":
            _GC_YOUNG_T0 = time.perf_counter()
        elif _GC_YOUNG_T0:
            GC_YOUNG_SECONDS.observe_key(
                (), time.perf_counter() - _GC_YOUNG_T0)
            _GC_YOUNG_T0 = 0.0
        return
    if phase == "start":
        ann = _trace_annotation(_ANNOTATION_PREFIX + "gc")
        ann.__enter__()
        _GC_OPEN.append((ann, time.perf_counter()))
    elif _GC_OPEN:
        ann, t0 = _GC_OPEN.pop()
        seconds = time.perf_counter() - t0
        GC_PAUSE_SECONDS.observe(seconds)
        ann.__exit__(None, None, None)
        # not behind a settle in progress: its own reclaim walk ends
        # here too, with the lock held by this very thread
        if seconds >= RESETTLE_PAUSE_S and _GC_LOCK.acquire(blocking=False):
            try:
                _settle_locked("gc", count=False)
            finally:
                _GC_LOCK.release()


def install_gc_hook() -> None:
    global _GC_USERS
    with _GC_LOCK:
        _GC_USERS += 1
        if _GC_USERS == 1:
            gc.callbacks.append(_on_gc)


def remove_gc_hook() -> None:
    """The last server's close also unfreezes: a process that goes on
    without a server collects as it did before the first one."""
    global _GC_USERS
    with _GC_LOCK:
        if not _GC_USERS:
            return
        _GC_USERS -= 1
        if _GC_USERS:
            return
        if _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)
        gc.unfreeze()
        GC_FROZEN_OBJECTS.set(0)


def settle_heap(at: str, reclaim: bool = False) -> None:
    """Freeze everything tracked so far into the permanent generation,
    so that full collections walk only what was allocated since. Called
    where the long-lived heap has just grown or been swapped (`at`, one
    of HEAP_SETTLE_SITES; "gc" is the hook's own, see above), never per
    batch. A no-op while no server holds the gc hook.

    Plain: gc.freeze(), three list merges, from any thread. Frozen
    objects are still freed by reference count; only cyclic garbage
    among them waits for the next reclaim. Young and full collections
    keep running over everything allocated after the settle.

    `reclaim`: unfreeze, one full collection, freeze again: one walk
    of the whole heap (it stops every thread, so never on a pump
    thread) that returns what earlier settles caught and has since
    died in a cycle: set-up garbage, and after a publish the outgoing
    snapshot, its handlers and traced programs. What an in-flight
    batch or an ORPHAN_DRAIN_S timer still references then is frozen
    again and goes at the next publish: one generation at most."""
    with _GC_LOCK:
        _settle_locked(at, reclaim)


def _settle_locked(at: str, reclaim: bool = False,
                   count: bool = True) -> None:
    if not _GC_USERS:
        return
    if reclaim:
        gc.unfreeze()
        gc.collect()
    gc.freeze()
    if count:
        # a walk of the permanent generation's list (35 ms at 0.7 M
        # objects on the chip's host): not for the hook, which may be
        # on a pump thread
        GC_FROZEN_OBJECTS.set(gc.get_freeze_count())
    HEAP_SETTLES.inc(at=at)


def gc_pause_snapshot(since: dict | None = None) -> dict:
    """Full collections seen by the hook ({"count", "sum_s"}, or the
    delta against an earlier snapshot `since`), the young ones under
    "young" (the same two keys), and the heap as the last settle at a
    named site left it: "frozen" (objects in the permanent generation,
    0 when not frozen; the hook's own settles add to them uncounted)
    and "settles" ({at: calls})."""
    _, total, n = GC_PAUSE_SECONDS.state()
    _, young_total, young_n = GC_YOUNG_SECONDS.state()
    if since is not None:
        total, n = total - since["sum_s"], n - since["count"]
        young = since.get("young", {"count": 0, "sum_s": 0.0})
        young_total -= young["sum_s"]
        young_n -= young["count"]
    return {"count": n, "sum_s": total,
            "young": {"count": young_n, "sum_s": young_total},
            "frozen": int(GC_FROZEN_OBJECTS.value()),
            "settles": {at: int(HEAP_SETTLES.value(at=at))
                        for at in HEAP_SETTLE_SITES}}


# -- the pump watch: a stall that no closed span can hold --------------
#
# A histogram of closed spans keeps a sum and a count: one residence of
# 2 s disappears into 1 900 ordinary ones, and a span that never closes
# is not there at all. Three clocks that do not need each other see it
# while it lasts, all on CLOCK_MONOTONIC (time.perf_counter() here is
# h2_frame.h:mono_ns() in the C++ front):
#   * each pump's open spans, in a slot (_PumpSlot; _Span writes it):
#     a pump whose top-level span is older than STALL_S is stalled.
#     Not so in take_wait, whose residence has no bound by design: a
#     pump with nothing to take is idle. There the front's gaps decide;
#   * a heartbeat (thread mixer-pump-watch, one a process while a
#     native front serves): it sleeps _TICK_S with the interpreter lock
#     released and observes how late it woke into
#     mixer_lock_wait_seconds: the wait of a ready thread for the lock.
#     A wake STALL_S late means nobody ran python for that long: the
#     first thing it does then is take every thread's stack, the
#     holder's as it stands just after it let go. (faulthandler's
#     watchdog would write them DURING the stall, with no lock, and
#     that is why it is not used: its walk of the thread list races
#     with threads that start and end, and a server's do: two of two
#     runs on the chip died of it, PERF.md PR 37.)
#   * the C++ front's gap counters (NativeMixerServer.gaps()): rows
#     that waited for a pump, a client that sent nothing, an IO thread
#     that did not run. The watch reads them once a tick, so a gap is
#     dated to the tick after it ended.
# When a stall closes the watch joins them into ONE forensics event
# "pump.stall" with a cause (stall_cause), one log line, and an
# observation of mixer_pump_stall_seconds{span}.

STALL_S = 0.2
_TICK_S = 0.05
# the ten spans that tile a pump's cycle
PUMP_TOP_LEVEL = ("take_wait", "wire_decode") + CHECK_STAGES + (
    "serialize", "send")

LOCK_WAIT_SECONDS = hostmetrics.default_registry.histogram(
    "mixer_lock_wait_seconds",
    "how late the pump watch's heartbeat woke from its sleep: the wait "
    "of a ready thread for the interpreter lock")
PUMP_STALL_SECONDS = hostmetrics.default_registry.histogram(
    "mixer_pump_stall_seconds",
    "residences of a pump in one top-level span that passed the stall "
    "threshold, whole; under take_wait the seconds rows waited or the "
    "client was silent, not the idle residence (label: span)")
for _name in PUMP_TOP_LEVEL:    # zero-series before the first stall
    PUMP_STALL_SECONDS.observe_key((("span", _name),), 0.0, 0)

# (wake time, lateness) of the last heartbeats (100 s of them): the
# beats that overlap a stall, and the exact maximum since a snapshot,
# which histogram buckets cannot give
_BEATS: collections.deque = collections.deque(maxlen=2048)
_STACK_FRAMES = 12      # innermost frames kept of a thread
_STACK_THREADS = 24


def stall_cause(span: str | None, lock_late_s: float, io_s: float,
                silent_s: float, starved_s: float,
                taking: bool) -> str:
    """Why a pump sat in `span` past STALL_S, first row that holds.
    `lock_late_s`: the heartbeat's largest lateness over the stall;
    `io_s` / `silent_s` / `starved_s`: the C++ front's gaps over it;
    `taking`: a pump was in take_wait."""
    if lock_late_s >= STALL_S:
        # nobody ran python. The IO thread needs no lock: if it stood
        # still too, the process was not scheduled (steal, compaction,
        # a stopped process); else a thread held the lock: the stacks
        # show where it stood as it let go
        return "process" if io_s > 0 else "lock"
    if span == "take_wait" and silent_s > 0:
        return "client"     # everything answered, nothing sent
    if span in ("h2d", "device_step"):
        return "device"     # retry and compile deltas say which
    if span == "send" or (starved_s > 0 and taking):
        return "front"      # rows waited while a pump asked for them
    return "host"   # python with the lock on offer: faults, a blocking call


def _open_spans(entry) -> tuple:
    """(the top-level entry, the innermost name) of a slot's chain."""
    inner = entry[0] if entry is not None else None
    top = None
    while entry is not None:
        if entry[0] in PUMP_TOP_LEVEL:
            top = entry
        entry = entry[2]
    return top, inner


def _live_stacks(everyone: bool = False) -> list:
    """[{"thread", "frames"}], innermost frame first, of the pumps and
    the threads that are no daemons; of `everyone` after a late wake,
    when any thread may have been the holder."""
    from istio_tpu.runtime import forensics

    idents = None if everyone else set(_PUMP_SLOTS) | {
        t.ident for t in threading.enumerate() if not t.daemon}
    return [{"thread": t["name"],
             "frames": t["stack"][::-1][:_STACK_FRAMES]}
            for t in forensics.thread_stacks(idents)["threads"]
            ][:_STACK_THREADS]


class _PumpWatch:
    """The heartbeat thread and what it keeps between ticks."""

    def __init__(self):
        self.users = 0
        self.fronts: tuple = ()     # the gaps() of the fronts that serve
        self._stop = threading.Event()
        # (time, counters) of the last ticks: what a stall's deltas are
        # taken against, back to before it began (6 s at 50 ms)
        self._marks: collections.deque = collections.deque(maxlen=128)
        # (wake time, every thread's stack) of the ticks that woke late
        self._late_stacks: collections.deque = collections.deque(maxlen=16)
        from istio_tpu.compiler import cache as compile_cache
        self._compiles = compile_cache.cache_event_counts
        self._stalls: dict = {}     # slot -> what detection saw
        self._spans: dict = {}      # slot -> (top, inner) a tick ago
        self._ann = None
        self._thread = threading.Thread(
            target=self._run, name="mixer-pump-watch", daemon=True)
        self._thread.start()

    def close(self) -> None:
        self._stop.set()
        self._thread.join()

    def _run(self) -> None:
        try:
            while True:
                asleep = time.perf_counter()
                if self._stop.wait(_TICK_S):
                    return
                now = time.perf_counter()
                late = max(now - asleep - _TICK_S, 0.0)
                # first, before anybody moves on: where the pumps are
                # and, after a late wake, where every thread stands
                # (reading source lines lends the lock)
                spans = {slot: _open_spans(slot.open)
                         for slot in list(_PUMP_SLOTS.values())}
                if late >= STALL_S:
                    self._late_stacks.append((now, _live_stacks(True)))
                LOCK_WAIT_SECONDS.observe_key((), late)
                _BEATS.append((now, late))
                try:
                    self._tick(now, late, spans)
                except Exception:   # the watch observes: it never ends
                    log.exception("pump watch tick failed")
        finally:
            if self._ann is not None:
                self._ann.__exit__(None, None, None)

    def _counters(self) -> dict:
        full, young = GC_PAUSE_SECONDS.state(), GC_YOUNG_SECONDS.state()
        compiles = self._compiles()
        out = {"gc_full": full[2], "gc_full_s": full[1],
               "gc_young": young[2], "gc_young_s": young[1],
               "cache_hits": compiles["hits"],
               "cache_misses": compiles["misses"],
               "device_retries": int(CHECK_DEVICE_RETRIES._value.get()),
               "starved_s": 0.0, "silent_s": 0.0, "io_s": 0.0}
        for gaps in self.fronts:
            for kind, gap in gaps().items():
                out[kind + "_s"] += gap["sum_ns"] / 1e9
        return out

    @staticmethod
    def _seen(slot, top, inner, spans: dict, now: float,
              stacks: tuple = ()) -> dict:
        """What a stall's event says of the tick that detected it."""
        return {"top": top, "pump": slot.pump if slot else None,
                "nested": inner, "stacks": list(stacks),
                "others": [{"pump": other.pump, "span": t[0],
                            "age_s": max(now - t[1], 0.0)}
                           for other, (t, _) in spans.items()
                           if other is not slot and t is not None]}

    def _tick(self, now: float, late: float, spans: dict) -> None:
        counters = self._counters()
        before = self._marks[-1][1] if self._marks else counters
        self._marks.append((now, counters))
        was, self._spans = self._spans, spans
        ended = []
        for slot, seen in list(self._stalls.items()):
            if spans.get(slot, (None, None))[0] is not seen["top"]:
                ended.append(self._stalls.pop(slot))
        for slot, (top, inner) in spans.items():
            if top is not None and top[0] != "take_wait" \
                    and slot not in self._stalls \
                    and now - top[1] >= STALL_S:
                # a late tick has taken everyone's stacks already
                self._stalls[slot] = self._seen(
                    slot, top, inner, spans, now,
                    () if late >= STALL_S else _live_stacks())
        # take_wait: not its age but a gap of the front's that ended
        # since the last tick, for the gap's own seconds. A silence:
        # every pump was idle. Rows that waited: only if no other
        # span's stall tells of them and a pump sat in take_wait all
        # the while (handed rows it could not come back with, or never
        # woken); pumps away in one short span after another are no
        # stall of any span (the front's `starved` counter has them)
        silent_s = counters["silent_s"] - before["silent_s"]
        starved_s = counters["starved_s"] - before["starved_s"]
        if silent_s > 0:
            ended.append(self._seen(None, ("take_wait", now - silent_s),
                                    None, spans, now))
        if starved_s > 0 and not ended and not self._stalls:
            for slot, (top, inner) in was.items():
                if top is not None and top[0] == "take_wait" \
                        and top[1] <= now - starved_s:
                    ended.append(self._seen(
                        slot, ("take_wait", now - starved_s), inner,
                        spans, now))
                    break
        for seen in ended:
            self._report(seen, now, counters)
        if late >= STALL_S and not ended and not self._stalls:
            # nobody ran python for that long, and no pump was in a
            # span old enough to say so
            self._report(self._seen(None, (None, now - late), None,
                                    spans, now), now, counters)
        if self._stalls and self._ann is None:
            self._ann = _trace_annotation(_ANNOTATION_PREFIX + "stall")
            self._ann.__enter__()
        elif not self._stalls and self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def _report(self, seen: dict, now: float, counters: dict) -> None:
        from istio_tpu.runtime import forensics

        span, t0 = seen["top"][:2]
        base = next((c for t, c in reversed(self._marks) if t <= t0),
                    self._marks[0][1])
        moved = {k: max(v - base[k], 0) for k, v in counters.items()}
        # the heartbeat that woke latest of those that overlap, and the
        # stacks it took as it woke
        lock_late, at = max(((late, t) for t, late in tuple(_BEATS)
                             if t >= t0 and t - late <= now),
                            default=(0.0, None))
        stacks = dict(self._late_stacks).get(at, seen["stacks"])
        taking = span == "take_wait" or any(
            other["span"] == "take_wait" for other in seen["others"])
        cause = stall_cause(span, lock_late, moved["io_s"],
                            moved["silent_s"], moved["starved_s"], taking)
        seconds = now - t0
        if span is not None:
            PUMP_STALL_SECONDS.observe_key((("span", span),), seconds)
        detail = {"cause": cause, "span": span, "seconds": seconds,
                  "lock_late_s": lock_late, **moved}
        if cause == "client":
            # a quiet front reads the same as a client that froze: a
            # short, all-numeric detail folds its events into one
            # entry, so they cannot push a publish out of the ring;
            # and no warning for what may be a quiet night
            forensics.record_event("pump.stall", coalesce_s=60.0, **detail)
            log.info("pump.stall cause=client span=take_wait "
                     "seconds=%.3f silent_s=%.3f", seconds,
                     moved["silent_s"])
            return
        forensics.record_event(
            "pump.stall", pump=seen["pump"], nested=seen["nested"],
            t0_ns=int(t0 * 1e9), t1_ns=int(now * 1e9),
            others=seen["others"], stacks=stacks, **detail)
        log.warning(
            "pump.stall cause=%s pump=%s span=%s nested=%s seconds=%.3f "
            "lock_late_s=%.3f starved_s=%.3f silent_s=%.3f io_s=%.3f "
            "gc_full_s=%.3f device_retries=%d compiles=%d",
            cause, seen["pump"], span, seen["nested"], seconds, lock_late,
            moved["starved_s"], moved["silent_s"], moved["io_s"],
            moved["gc_full_s"], moved["device_retries"],
            moved["cache_hits"] + moved["cache_misses"])


_WATCH_LOCK = threading.Lock()
_WATCH: _PumpWatch | None = None


def pump_watch_start(gaps: Callable[[], dict] | None = None) -> None:
    """A native front starts serving: the watch runs from the first
    such call to the last pump_watch_stop (tests run several servers).
    `gaps`: the front's NativeMixerServer.gaps, read once a tick."""
    global _WATCH
    with _WATCH_LOCK:
        if _WATCH is None:
            _WATCH = _PumpWatch()
        _WATCH.users += 1
        if gaps is not None:
            _WATCH.fronts += (gaps,)


def pump_watch_stop(gaps: Callable[[], dict] | None = None) -> None:
    """The last one joins the thread."""
    global _WATCH
    with _WATCH_LOCK:
        watch = _WATCH
        if watch is None:
            return
        watch.fronts = tuple(g for g in watch.fronts if g != gaps)
        watch.users -= 1
        if not watch.users:
            _WATCH = None
            watch.close()


def pump_watch_snapshot(since: dict | None = None) -> dict:
    """What the watch has seen, or the delta against an earlier
    snapshot `since`: {"lock_wait": {"count", "sum_s", "max_s"} (the
    heartbeat's lateness; `max_s` of the last 2 048 beats at most),
    "stalls": {span: {"count", "sum_s"}} (mixer_pump_stall_seconds),
    "events": the pump.stall events' details, oldest first (`n` > 1: a
    quiet front's, folded)}."""
    from istio_tpu.runtime import forensics

    t0 = since["t"] if since is not None else 0.0
    now = time.perf_counter()
    _, total, n = LOCK_WAIT_SECONDS.state()
    stalls = {}
    for name in PUMP_TOP_LEVEL:
        _, span_total, span_n = PUMP_STALL_SECONDS.state(span=name)
        stalls[name] = {"count": span_n, "sum_s": span_total}
    if since is not None:
        total -= since["lock_wait"]["sum_s"]
        n -= since["lock_wait"]["count"]
        for name, was in since["stalls"].items():
            stalls[name]["count"] -= was["count"]
            stalls[name]["sum_s"] -= was["sum_s"]
    return {
        "t": now,
        "lock_wait": {"count": n, "sum_s": total,
                      "max_s": max((late for t, late in tuple(_BEATS)
                                    if t > t0), default=0.0)},
        "stalls": stalls,
        "events": [dict(e["detail"], n=e["n"])
                   for e in forensics.EVENTS.snapshot("pump.stall", limit=0)
                   if e["t"] > t0]}


def observe_check_e2e(seconds: float, n: int = 1) -> None:
    """End-to-end observation of `n` requests that waited `seconds`
    each (a pre-formed batch's rows share its wall: one call a batch,
    counted a row a request); gauges refresh lazily via
    refresh_latency_gauges() (sorting the window per request would put
    an O(n log n) on the hot path)."""
    if n > 0:
        CHECK_E2E_SECONDS.observe_key((), seconds, n)
        CHECK_WINDOW.observe(seconds, n)


def refresh_latency_gauges() -> dict:
    """Recompute the sliding-window percentile gauges + SLO gauge from
    the current window. Called by scrape-rate readers (the introspect
    /metrics handler, the SLO evaluator) — never per request."""
    p50, p95, p99 = CHECK_WINDOW.quantiles((0.50, 0.95, 0.99))
    p50_ms, p95_ms, p99_ms = p50 * 1e3, p95 * 1e3, p99 * 1e3
    CHECK_P50_MS.set(p50_ms)
    CHECK_P95_MS.set(p95_ms)
    CHECK_P99_MS.set(p99_ms)
    # empty window → vacuously under target: an idle/fresh server is
    # not violating its SLO, and alerting on ==0 must not fire before
    # the first request (mask on the e2e count for 'no data')
    under = not len(CHECK_WINDOW) or p99_ms <= CHECK_P99_TARGET_MS
    CHECK_SLO_GAUGE.set(1.0 if under else 0.0)
    return {"p50_ms": p50_ms, "p95_ms": p95_ms, "p99_ms": p99_ms,
            "n_window": len(CHECK_WINDOW),
            "n_total": CHECK_WINDOW.total,
            "target_ms": CHECK_P99_TARGET_MS,
            "under_target": under}


def reset_latency_window() -> None:
    """Drop windowed observations (scenario boundaries — a
    saturation phase's queueing tail must not pollute the light
    phase's live p99). Histograms keep accumulating; only the
    sliding-window gauges reset."""
    CHECK_WINDOW.reset()


def stage_baseline() -> dict:
    """Subtraction token for latency_snapshot(since=...): the stage +
    e2e histogram states at a window's start. The histograms are
    process-lifetime cumulative (prometheus semantics); per-SCENARIO
    readings (a benchmark window) must delta against a baseline or the
    previous phase's ~10k batches drown the window's few hundred."""
    token = {stage: CHECK_STAGE_SECONDS.state(stage=stage)
             for stage in CHECK_STAGES}
    token["__e2e__"] = CHECK_E2E_SECONDS.state()
    token["__spans__"] = {
        labels["span"]: PUMP_SPAN_SECONDS.state(**labels)
        for labels in PUMP_SPAN_SECONDS.label_sets()}
    return token


def _delta(state, base):
    counts, total, n = state
    bcounts, btotal, bn = base
    if bcounts:
        counts = [c - b for c, b in zip(counts, bcounts)] \
            if counts else []
    return counts, total - btotal, n - bn


def latency_snapshot(since: dict | None = None) -> dict:
    """Stage decomposition + live percentiles as one JSON-able dict —
    what /debug/queues serves and the benchmark's per-layer readers
    (benchmark/spans.py) reduce. `since`: a
    stage_baseline() token; readings then cover only the window after
    it (quantiles computed from delta bucket counts)."""
    from istio_tpu.utils.metrics import quantile_from_counts

    empty = ([], 0.0, 0)
    stages: dict[str, dict] = {}
    h = CHECK_STAGE_SECONDS
    for stage in CHECK_STAGES:
        counts, total, n = h.state(stage=stage)
        if since is not None:
            counts, total, n = _delta((counts, total, n),
                                      since.get(stage, empty))
        if not n:
            continue
        stages[stage] = {
            "count": n,
            "sum_ms": round(total * 1e3, 3),
            "p50_ms": round(quantile_from_counts(
                h.buckets, counts, n, 0.5) * 1e3, 3),
            "p99_ms": round(quantile_from_counts(
                h.buckets, counts, n, 0.99) * 1e3, 3),
        }
    spans: dict[str, dict] = {}
    span_base = since.get("__spans__", {}) if since is not None else {}
    for labels in PUMP_SPAN_SECONDS.label_sets():
        name = labels["span"]
        _, total, n = _delta(PUMP_SPAN_SECONDS.state(**labels),
                             span_base.get(name, empty))
        if n:
            spans[name] = {"count": n, "sum_ms": total * 1e3}
    e2e = CHECK_E2E_SECONDS.state()
    if since is not None:
        e2e = _delta(e2e, since.get("__e2e__", empty))
    return {
        "stages": stages,
        "spans": spans,
        "e2e_count": e2e[2],
        "e2e_sum_ms": round(e2e[1] * 1e3, 3),
        "live": refresh_latency_gauges(),
    }


def serving_counters() -> dict:
    """Snapshot of the serving-path counters as a plain dict (read by
    the auditor and the soak gates)."""
    hist: dict[str, int] = {}
    for i, b in enumerate(CHECK_BATCH_SIZE._upper_bounds):
        # prometheus_client stores per-bucket (non-cumulative) counts
        cur = int(CHECK_BATCH_SIZE._buckets[i].get())
        label = "inf" if b == float("inf") else str(int(b))
        if cur:
            hist[label] = cur
    decoded = int(CHECK_REQUESTS._value.get())
    sent = int(CHECK_RESPONSES._value.get())
    return {
        "requests_decoded": decoded,
        "responses_sent": sent,
        "in_flight": decoded - sent,
        "batches_formed": sum(hist.values()),
        "batch_rows": int(CHECK_BATCH_SIZE._sum.get()),
        "batch_size_hist": hist,
        "report_batch_rows": int(REPORT_BATCH_SIZE._sum.get()),
        "report_batches_formed": int(
            REPORT_BATCH_SIZE._buckets and sum(
                int(b.get()) for b in REPORT_BATCH_SIZE._buckets)),
    }


# -- telemetry ingestion plane (the REPORT half of Mixer's API) -------
#
# Stage semantics, mirroring the six-stage Check() decomposition above
# (one observation per unit of pipeline work; counts differ by design —
# wire_decode is per-RPC, coalesce_wait/tensorize/device_field_eval/
# intern_decode per coalesced batch/chunk, adapter_dispatch per
# dispatched batch):
#   wire_decode       — ReportRequest parse + per-record delta decode
#                       into bags (front side, per RPC)
#   coalesce_wait     — oldest record's enqueue -> batch start in the
#                       cross-RPC record coalescer (the report batcher)
#   tensorize         — record bags -> AttributeBatch (+ns ids)
#   device_field_eval — the packed_report device trip (rule resolve +
#                       every instance-field expression for every
#                       record in one pull)
#   intern_decode     — pulled id planes -> Python values (one
#                       unique-id pass per chunk) + seal
#   adapter_dispatch  — host adapter fan-out (handle_report calls)
REPORT_STAGES = ("wire_decode", "coalesce_wait", "tensorize",
                 "device_field_eval", "intern_decode",
                 "adapter_dispatch")

REPORT_STAGE_SECONDS = hostmetrics.default_registry.histogram(
    "mixer_report_stage_seconds",
    "per-unit report ingestion stage latency (label: stage; see "
    "runtime/monitor.py REPORT_STAGES for unit semantics)")

# Record conservation (the ingestion plane's correctness invariant):
# every record entering the plane ends in EXACTLY one of exported /
# rejected, so accepted == exported + rejected holds at quiescence and
# in_flight = accepted - exported - rejected is never negative.
# Unlabeled counters expose at zero from the first scrape; the labeled
# rejection family pre-touches its reasons below.
REPORT_REJECT_REASONS = ("queue_full", "unavailable", "deadline",
                         "error")
REPORT_REQUESTS = prometheus_client.Counter(
    "mixer_grpc_report_requests", "Report RPCs decoded (all fronts)",
    registry=REGISTRY)
REPORT_RESPONSES = prometheus_client.Counter(
    "mixer_grpc_report_responses",
    "Report responses sent (all fronts)", registry=REGISTRY)
REPORT_RECORDS_ACCEPTED = prometheus_client.Counter(
    "mixer_report_records_accepted_total",
    "report records entering the ingestion plane (pre-admission; "
    "conservation: accepted == exported + rejected at quiescence)",
    registry=REGISTRY)
REPORT_RECORDS_EXPORTED = prometheus_client.Counter(
    "mixer_report_records_exported_total",
    "report records whose batch completed adapter dispatch",
    registry=REGISTRY)
REPORT_RECORDS_REJECTED = prometheus_client.Counter(
    "mixer_report_records_rejected_total",
    "report records resolved with a typed rejection, by reason "
    "(queue_full=RESOURCE_EXHAUSTED shed, unavailable=draining/dead "
    "coalescer, deadline, error=batch failure)", ["reason"],
    registry=REGISTRY)
for _r in REPORT_REJECT_REASONS:
    REPORT_RECORDS_REJECTED.labels(reason=_r)

# per-template record counts (label appears on first dispatch; the
# family itself zero-exposes via the homegrown registry's counter)
REPORT_TEMPLATE_RECORDS = hostmetrics.default_registry.counter(
    "mixer_report_template_records_total",
    "report instances dispatched to adapters, by template")
REPORT_TEMPLATE_RECORDS.inc(0)   # zero-series before the first record

# adapter-export accounting, by exporter (qualified handler name):
# records delivered, drops (handler exceptions — safeDispatch absorbs
# them, this is their only trace besides the log), last dispatch wall
# seconds. Queue depth for the plane is the coalescer's (the export
# fan-out runs inside the report batch; /debug/report joins both).
REPORT_EXPORTER_RECORDS = hostmetrics.default_registry.counter(
    "mixer_report_exporter_records_total",
    "report instances delivered per exporter (qualified handler name)")
REPORT_EXPORTER_DROPS = hostmetrics.default_registry.counter(
    "mixer_report_exporter_drops_total",
    "report dispatches dropped by adapter exceptions, per exporter")
REPORT_EXPORTER_LAG_MS = hostmetrics.default_registry.gauge(
    "mixer_report_exporter_last_dispatch_ms",
    "wall milliseconds of the exporter's most recent handle_report")
REPORT_EXPORTER_RECORDS.inc(0)
REPORT_EXPORTER_DROPS.inc(0)
REPORT_EXPORTER_LAG_MS.set(0.0)

# recent drop reasons (bounded; /debug/report's "what got rejected
# lately" pane — a typed shed the client saw must be explainable from
# the server side without log spelunking)
_REPORT_DROPS: collections.deque = collections.deque(maxlen=32)
_REPORT_DROPS_LOCK = threading.Lock()

# per-exporter point-in-time stats for /debug/report (the counter
# families above are the scrape surface; this dict carries the
# JSON-able view: wall stamps don't belong in counters)
_EXPORTER_STATS: dict = {}


def observe_report_stage(stage: str, seconds: float) -> None:
    REPORT_STAGE_SECONDS.observe(seconds, stage=stage)


def report_accepted(n: int = 1) -> None:
    REPORT_RECORDS_ACCEPTED.inc(n)


def report_exported(n: int = 1) -> None:
    REPORT_RECORDS_EXPORTED.inc(n)


def report_rejected(n: int, reason: str, detail: str = "") -> None:
    if reason not in REPORT_REJECT_REASONS:
        reason = "error"
    REPORT_RECORDS_REJECTED.labels(reason=reason).inc(n)
    with _REPORT_DROPS_LOCK:
        _REPORT_DROPS.append({
            "wall": time.time(), "reason": reason,
            "records": int(n), "detail": detail[:200]})


def report_record_done(fut) -> None:
    """Single accounting home for coalesced report records: attached
    as a done-callback to every future the report coalescer returns,
    so every accepted record is counted exported or typed-rejected
    EXACTLY once — the conservation invariant is enforced where
    futures resolve, not re-derived per code path."""
    from istio_tpu.runtime import resilience

    try:
        exc = fut.exception()
    except BaseException as cancel:   # cancelled futures carry no exc
        report_rejected(1, "error",
                        f"cancelled: {type(cancel).__name__}")
        return
    if exc is None:
        report_exported(1)
    elif isinstance(exc, resilience.ResourceExhaustedError):
        report_rejected(1, "queue_full", str(exc))
    elif isinstance(exc, resilience.DeadlineExceededError):
        report_rejected(1, "deadline", str(exc))
    elif isinstance(exc, resilience.UnavailableError):
        report_rejected(1, "unavailable", str(exc))
    else:
        report_rejected(1, "error",
                        f"{type(exc).__name__}: {exc}")


def note_adapter_export(exporter: str, template: str, n_records: int,
                        seconds: float, error: bool = False) -> None:
    """One adapter handle_report outcome (dispatcher.report)."""
    if error:
        REPORT_EXPORTER_DROPS.inc(1, exporter=exporter)
    else:
        REPORT_EXPORTER_RECORDS.inc(n_records, exporter=exporter)
    REPORT_EXPORTER_LAG_MS.set(seconds * 1e3, exporter=exporter)
    with _REPORT_DROPS_LOCK:
        st = _EXPORTER_STATS.setdefault(exporter, {
            "records": 0, "drops": 0, "last_dispatch_ms": 0.0,
            "last_wall": 0.0, "templates": {}})
        if error:
            st["drops"] += 1
        else:
            st["records"] += n_records
            st["templates"][template] = \
                st["templates"].get(template, 0) + n_records
        st["last_dispatch_ms"] = round(seconds * 1e3, 3)
        st["last_wall"] = time.time()


def report_conservation(since: dict | None = None) -> dict:
    """The invariant, readable: accepted == exported + rejected at
    quiescence; in_flight is the (transient) difference. `exact` is
    True only when the plane is fully drained — the form the smoke
    gate and shutdown assertions check. `since`: a previous
    report_conservation() reading — the counters are process-lifetime
    cumulative, so per-scenario checks (soak phases, tests sharing a
    process) must delta against their own baseline."""
    accepted = int(REPORT_RECORDS_ACCEPTED._value.get())
    exported = int(REPORT_RECORDS_EXPORTED._value.get())
    rejected = {r: int(REPORT_RECORDS_REJECTED.labels(
        reason=r)._value.get()) for r in REPORT_REJECT_REASONS}
    if since is not None:
        accepted -= since.get("accepted", 0)
        exported -= since.get("exported", 0)
        base_rej = since.get("rejected", {})
        rejected = {r: v - base_rej.get(r, 0)
                    for r, v in rejected.items()}
    rej_total = sum(rejected.values())
    return {
        "accepted": accepted,
        "exported": exported,
        "rejected": rejected,
        "rejected_total": rej_total,
        "in_flight": accepted - exported - rej_total,
        "exact": accepted == exported + rej_total,
    }


def report_stage_baseline() -> dict:
    """Subtraction token for report_latency_snapshot(since=...) — same
    delta-window discipline as stage_baseline()."""
    return {stage: REPORT_STAGE_SECONDS.state(stage=stage)
            for stage in REPORT_STAGES}


def report_latency_snapshot(since: dict | None = None) -> dict:
    """Six-stage report pipeline decomposition (p50/p95/p99 per stage)
    as one JSON-able dict — what /debug/report serves and the report
    smoke gates."""
    from istio_tpu.utils.metrics import quantile_from_counts

    empty = ([], 0.0, 0)
    stages: dict[str, dict] = {}
    h = REPORT_STAGE_SECONDS
    for stage in REPORT_STAGES:
        counts, total, n = h.state(stage=stage)
        if since is not None:
            counts, total, n = _delta((counts, total, n),
                                      since.get(stage, empty))
        if not n:
            continue
        stages[stage] = {
            "count": n,
            "sum_ms": round(total * 1e3, 3),
            "p50_ms": round(quantile_from_counts(
                h.buckets, counts, n, 0.5) * 1e3, 3),
            "p95_ms": round(quantile_from_counts(
                h.buckets, counts, n, 0.95) * 1e3, 3),
            "p99_ms": round(quantile_from_counts(
                h.buckets, counts, n, 0.99) * 1e3, 3),
        }
    return {"stages": stages}


def report_counters() -> dict:
    """Ingestion-plane snapshot for /debug/report and the report smoke:
    conservation + per-template record counts + per-exporter stats +
    recent drop reasons. Always JSON-able; zero-shaped before the
    first record (the view must serve on an idle server)."""
    with _REPORT_DROPS_LOCK:
        drops = list(_REPORT_DROPS)
        exporters = {k: {**v, "templates": dict(v["templates"])}
                     for k, v in _EXPORTER_STATS.items()}
    templates = {}
    with REPORT_TEMPLATE_RECORDS._lock:   # snapshot vs live inc()s
        tmpl_values = dict(REPORT_TEMPLATE_RECORDS._values)
    for labels, v in tmpl_values.items():
        name = dict(labels).get("template")
        if name:
            templates[name] = int(v)
    return {
        "rpcs_decoded": int(REPORT_REQUESTS._value.get()),
        "responses_sent": int(REPORT_RESPONSES._value.get()),
        "conservation": report_conservation(),
        "templates": templates,
        "exporters": exporters,
        "recent_drops": drops,
    }


# -- sharded serving plane (istio_tpu/sharding) ----------------------
#
# Stage semantics (one observation per unit of router work;
# bank_check is per (batch, bank) so a batch spanning B banks
# contributes B observations — the device-trip fan-out IS the cost
# being attributed):
#   shard_dispatch — namespace extraction + row bucketing, per batch
#   bank_check     — one bank's full fused check on its sub-batch
#                    (tensorize → device trip → overlay, the existing
#                    CHECK stages decompose it further)
#   fold           — response scatter back into row order + bank-local
#                    → global deny-index remap, per batch
SHARD_STAGES = ("shard_dispatch", "bank_check", "fold")

SHARD_STAGE_SECONDS = hostmetrics.default_registry.histogram(
    "mixer_shard_stage_seconds",
    "per-batch sharded-serving stage latency (label: stage; see "
    "runtime/monitor.py SHARD_STAGES for unit semantics)")
REPLICA_BATCH_SECONDS = hostmetrics.default_registry.histogram(
    "mixer_replica_batch_seconds",
    "per-replica served batch wall seconds (label: replica)")
REPLICA_ROWS = hostmetrics.default_registry.counter(
    "mixer_replica_rows_total",
    "check rows served per replica lane (label: replica)")
REPLICA_ROWS.inc(0)   # zero-series before the first routed batch


def observe_shard_stage(stage: str, seconds: float) -> None:
    SHARD_STAGE_SECONDS.observe(seconds, stage=stage)


def observe_replica_batch(replica: int, seconds: float,
                          rows: int) -> None:
    REPLICA_BATCH_SECONDS.observe(seconds, replica=str(replica))
    REPLICA_ROWS.inc(rows, replica=str(replica))


def shard_stage_baseline() -> dict:
    """Subtraction token for shard_latency_snapshot(since=...) — the
    same delta-window discipline as stage_baseline()."""
    return {stage: SHARD_STAGE_SECONDS.state(stage=stage)
            for stage in SHARD_STAGES}


def shard_latency_snapshot(since: dict | None = None) -> dict:
    """Sharded-path stage decomposition (count/sum/p50/p99 per stage)
    as one JSON-able dict — /debug/shards' `stages` pane."""
    from istio_tpu.utils.metrics import quantile_from_counts

    empty = ([], 0.0, 0)
    stages: dict[str, dict] = {}
    h = SHARD_STAGE_SECONDS
    for stage in SHARD_STAGES:
        counts, total, n = h.state(stage=stage)
        if since is not None:
            counts, total, n = _delta((counts, total, n),
                                      since.get(stage, empty))
        if not n:
            continue
        stages[stage] = {
            "count": n,
            "sum_ms": round(total * 1e3, 3),
            "p50_ms": round(quantile_from_counts(
                h.buckets, counts, n, 0.5) * 1e3, 3),
            "p99_ms": round(quantile_from_counts(
                h.buckets, counts, n, 0.99) * 1e3, 3),
        }
    return {"stages": stages}


def replica_snapshot() -> dict:
    """Per-replica batch latency + row counts for /debug/shards —
    zero-shaped ({} lanes) before the first routed batch."""
    from istio_tpu.utils.metrics import quantile_from_counts

    out: dict[str, dict] = {}
    h = REPLICA_BATCH_SECONDS
    for lab in h.label_sets():
        rep = lab.get("replica")
        if rep is None:
            continue
        counts, total, n = h.state(replica=rep)
        if not n:
            continue
        out[rep] = {
            "batches": n,
            "sum_ms": round(total * 1e3, 3),
            "p50_ms": round(quantile_from_counts(
                h.buckets, counts, n, 0.5) * 1e3, 3),
            "p95_ms": round(quantile_from_counts(
                h.buckets, counts, n, 0.95) * 1e3, 3),
            "p99_ms": round(quantile_from_counts(
                h.buckets, counts, n, 0.99) * 1e3, 3),
        }
    return out


# -- pilot discovery serving plane (istio_tpu/pilot/discovery.py) -----
#
# Stage semantics (one observation per unit of discovery work; the
# units differ by design — the decomposition's job is attributing a
# slow publish or a slow poll to its stage):
#   snapshot_build — registry/config freeze + per-namespace content
#                    digests + per-host indexes, per publish
#   scope_plan     — namespace→shard delta planning (sharding/planner
#                    reuse), per publish
#   invalidate     — snapshot diff + scoped cache sweep + shard
#                    version bumps/wakeups, per publish
#   route_eval     — ONE batched source-admission device step shared
#                    by every pending node group (route_nfa.
#                    RouteScopeProgram.admit_rows), per batch
#   generate       — config JSON assembly + cache fill, per batch of
#                    node groups
#   serve          — cache lookup → response bytes, per endpoint call
DISCOVERY_STAGES = ("snapshot_build", "scope_plan", "invalidate",
                    "route_eval", "generate", "serve")

DISCOVERY_STAGE_SECONDS = hostmetrics.default_registry.histogram(
    "pilot_discovery_stage_seconds",
    "per-unit discovery serving stage latency (label: stage; see "
    "runtime/monitor.py DISCOVERY_STAGES for unit semantics)")
DISCOVERY_PUSH_FANOUT_SECONDS = hostmetrics.default_registry.histogram(
    "pilot_discovery_push_fanout_seconds",
    "delta-push fan-out latency: snapshot publish -> a parked "
    "version-watcher waking with the new generation (only watchers "
    "already waiting when the publish landed count — a late watcher "
    "measures its own arrival, not the push)")
# cache events, zero-shaped per the promtext doctrine: a dashboard
# must distinguish "never invalidated" from "counter missing".
#   hit/miss     — per endpoint call against the current generation
#   carried      — entries re-stamped to a new generation because
#                  their namespace deps did NOT change (the scoped-
#                  invalidation win, counted per publish sweep)
#   invalidated  — entries dropped by a publish sweep
DISCOVERY_CACHE_EVENTS = ("hit", "miss", "carried", "invalidated")
DISCOVERY_CACHE = hostmetrics.default_registry.counter(
    "pilot_discovery_cache_events_total",
    "discovery response-cache events, by event (hit/miss per call, "
    "carried/invalidated per publish sweep)")
DISCOVERY_GENERATION = hostmetrics.default_registry.gauge(
    "pilot_discovery_generation",
    "active discovery snapshot generation")
for _e in DISCOVERY_CACHE_EVENTS:
    DISCOVERY_CACHE.inc(0, event=_e)


def observe_discovery_stage(stage: str, seconds: float) -> None:
    DISCOVERY_STAGE_SECONDS.observe(seconds, stage=stage)


def observe_discovery_push(seconds: float) -> None:
    DISCOVERY_PUSH_FANOUT_SECONDS.observe(seconds)


def note_discovery_cache(event: str, n: int = 1) -> None:
    if n:
        DISCOVERY_CACHE.inc(n, event=event)


def set_discovery_generation(version: int) -> None:
    DISCOVERY_GENERATION.set(float(version))


def discovery_latency_snapshot() -> dict:
    """Discovery stage decomposition + push fan-out percentiles as one
    JSON-able dict — /debug/discovery's `stages` pane and the SLO
    evaluator's push-fan-out objective."""
    from istio_tpu.utils.metrics import quantile_from_counts

    stages: dict[str, dict] = {}
    h = DISCOVERY_STAGE_SECONDS
    for stage in DISCOVERY_STAGES:
        counts, total, n = h.state(stage=stage)
        if not n:
            continue
        stages[stage] = {
            "count": n,
            "sum_ms": round(total * 1e3, 3),
            "p50_ms": round(quantile_from_counts(
                h.buckets, counts, n, 0.5) * 1e3, 3),
            "p99_ms": round(quantile_from_counts(
                h.buckets, counts, n, 0.99) * 1e3, 3),
        }
    ph = DISCOVERY_PUSH_FANOUT_SECONDS
    counts, total, n = ph.state()
    push = {"count": n}
    if n:
        push.update({
            "p50_ms": round(quantile_from_counts(
                ph.buckets, counts, n, 0.5) * 1e3, 3),
            "p99_ms": round(quantile_from_counts(
                ph.buckets, counts, n, 0.99) * 1e3, 3),
        })
    return {"stages": stages, "push": push}


# -- mesh audit plane (runtime/audit.py) -------------------------------
# Families for the background invariant auditor. Zero-shaped per the
# promtext doctrine: every (invariant, status) series a dashboard can
# alert on must exist BEFORE the first evaluation — "no audit data" and
# "audit never ran" are different incidents.
AUDIT_INVARIANTS = ("report_conservation", "check_accounting",
                    "quota_conservation", "grant_coherence",
                    "plane_agreement", "routing_conservation")
AUDIT_STATUSES = ("ok", "degraded", "violated")
FAULT_KINDS = ("wedge", "device", "oracle", "adapter", "quota",
               "discovery")

AUDIT_CHECKS = prometheus_client.Counter(
    "mixer_audit_checks", "audit evaluations per invariant per verdict",
    ["invariant", "status"], registry=REGISTRY)
AUDIT_VIOLATIONS = prometheus_client.Counter(
    "mixer_audit_violations",
    "transitions of an invariant into the violated state",
    ["invariant"], registry=REGISTRY)
AUDIT_EVALUATIONS = prometheus_client.Counter(
    "mixer_audit_evaluations", "full auditor passes", registry=REGISTRY)
AUDIT_HEALTHY = prometheus_client.Gauge(
    "mixer_audit_healthy",
    "1 while no mesh invariant is in the violated state (the "
    "/readyz-adjacent audit verdict)", registry=REGISTRY)
FAULT_INJECTIONS = prometheus_client.Counter(
    "mixer_fault_explainability_injections",
    "chaos injections registered with the explainability scorer",
    ["kind"], registry=REGISTRY)
FAULT_MATCHED = prometheus_client.Counter(
    "mixer_fault_explainability_matched",
    "chaos injections matched to a forensics exemplar/event in window",
    ["kind"], registry=REGISTRY)
FAULT_EXPLAINABILITY = prometheus_client.Gauge(
    "mixer_fault_explainability_rate",
    "matched / (matched + expired-unmatched) chaos injections; "
    "vacuously 1.0 with no injections", registry=REGISTRY)
for _inv in AUDIT_INVARIANTS:
    AUDIT_VIOLATIONS.labels(invariant=_inv)
    for _st in AUDIT_STATUSES:
        AUDIT_CHECKS.labels(invariant=_inv, status=_st)
for _k in FAULT_KINDS:
    FAULT_INJECTIONS.labels(kind=_k)
    FAULT_MATCHED.labels(kind=_k)
AUDIT_HEALTHY.set(1.0)
FAULT_EXPLAINABILITY.set(1.0)


def audit_counters() -> dict:
    """One JSON-able reading of the audit + explainability families —
    read by /debug/audit, the audit smoke and the soak gates."""
    checks = {inv: {st: int(AUDIT_CHECKS.labels(
        invariant=inv, status=st)._value.get())
        for st in AUDIT_STATUSES} for inv in AUDIT_INVARIANTS}
    return {
        "evaluations": int(AUDIT_EVALUATIONS._value.get()),
        "healthy": bool(AUDIT_HEALTHY._value.get() >= 1.0),
        "checks": checks,
        "violations": {inv: int(AUDIT_VIOLATIONS.labels(
            invariant=inv)._value.get()) for inv in AUDIT_INVARIANTS},
        "explainability_rate": float(
            FAULT_EXPLAINABILITY._value.get()),
        "injections": {k: int(FAULT_INJECTIONS.labels(
            kind=k)._value.get()) for k in FAULT_KINDS},
        "matched": {k: int(FAULT_MATCHED.labels(
            kind=k)._value.get()) for k in FAULT_KINDS},
    }


@contextlib.contextmanager
def resolve_timer():
    RESOLVE_COUNT.inc()
    t0 = time.perf_counter()
    try:
        yield
    finally:
        RESOLVE_DURATION.observe(time.perf_counter() - t0)


@contextlib.contextmanager
def dispatch_timer():
    DISPATCH_COUNT.inc()
    t0 = time.perf_counter()
    try:
        yield
    finally:
        DISPATCH_DURATION.observe(time.perf_counter() - t0)
