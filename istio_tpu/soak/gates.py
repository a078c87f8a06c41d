"""Recovery gates — evaluated from EXISTING surfaces only.

Nothing here adds instrumentation: the gates read the audit plane
(srv.audit.evaluate), the monitor counter families (deltas against a
pre-soak baseline, because the families are process-lifetime
cumulative and survive a RuntimeServer restart), the grant watermark,
and the fleet's client-side ledgers. A soak passes when, after the
storm clears:

  gate_conservation      report plane exact (accepted == exported +
                         rejected, in_flight 0) over the soak window
  gate_audit_ok          all six invariants ok, mixer_audit_healthy 1
  gate_explainability    mixer_fault_explainability_rate == 1.0 with
                         nothing pending — every injected fault
                         explained from forensics evidence alone
  gate_fault_kinds       >= min_kinds distinct injected kinds matched
  gate_no_stale_grants   grant watermark coherent (nothing issued
                         beyond the live generation) + the audited
                         grant_coherence invariant ok
  gate_plane_agreement   discovery <-> mixer agreement held live
  gate_client_accounting the per-sidecar outcome ledgers sum to the
                         server-side mixer_* front accounting
  gate_recovered         audit reached no-violated + fully-explained
                         under live traffic within the bound
                         (soak_recovery_s); strict all-ok is
                         re-asserted post-quiesce by gate_audit_ok
  gate_quiet_after       zero NEW violations after the recovery point
"""
from __future__ import annotations

import time

from istio_tpu.runtime import monitor


def snapshot_baselines() -> dict:
    """Pre-soak counter baselines (process-lifetime families)."""
    return {
        "report": monitor.report_conservation(),
        "serving": monitor.serving_counters(),
        "audit": monitor.audit_counters(),
    }


def wait_quiesce(base: dict | None = None, timeout_s: float = 20.0,
                 poll_s: float = 0.02) -> bool:
    """Drain wait: report plane in_flight → 0, deltaed against the
    soak baseline (the families are process-global — a sibling test's
    residue must not wedge this wait)."""
    since = (base or {}).get("report")
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if not monitor.report_conservation(since=since)["in_flight"]:
            return True
        time.sleep(poll_s)
    return not monitor.report_conservation(since=since)["in_flight"]


def wait_recovery(audit, timeout_s: float = 30.0,
                  poll_s: float = 0.2) -> dict:
    """Poll the auditor until no invariant is violated AND the
    explainability ledger has nothing pending (rate 1.0).

    This runs with the fleet still sending: a typed-covered residue
    (e.g. deadline-expired wire RPCs that never get per-row
    responses) legitimately reads `degraded (transient)` for as long
    as traffic keeps the counter tuple moving — the auditor only
    promotes it to steady-state ok once the reading freezes, which
    cannot happen under live load. So the live recovery bar is
    "nothing violated + every injection explained"; the strict
    every-check-ok bar is asserted post-quiesce by evaluate_gates().
    soak_recovery_s is measured from entry (the caller invokes this
    at storm end)."""
    t0 = time.monotonic()
    last = None
    while time.monotonic() - t0 < timeout_s:
        last = audit.evaluate()
        ex = last["explainability"]
        none_violated = all(c["status"] != "violated"
                            for c in last["checks"])
        if none_violated and last["healthy"] and ex["rate"] == 1.0 \
                and not ex["pending"]:
            return {"recovered": True,
                    "soak_recovery_s":
                        round(time.monotonic() - t0, 3),
                    "snapshot": last}
        time.sleep(poll_s)
    return {"recovered": False,
            "soak_recovery_s": round(time.monotonic() - t0, 3),
            "snapshot": last}


def _matched_kinds(ex: dict) -> set:
    return {r["kind"] for r in ex.get("records", ()) if r["matched"]}


def evaluate_gates(srv, fleet_totals: dict, base: dict, *,
                   recovery: dict, min_kinds: int = 3,
                   restarted: bool = False,
                   settle_evals: int = 3,
                   settle_sleep_s: float = 0.25) -> dict:
    """One verdict per gate + the soak_* metrics. Call AFTER the fleet
    stopped and wait_quiesce() passed; `recovery` is wait_recovery()'s
    result; `restarted` relaxes the client-accounting identity to the
    inequality (transport-level failures during the bounce never
    reached the server)."""
    gates: dict[str, bool] = {}
    detail: dict = {}

    cons = monitor.report_conservation(since=base["report"])
    gates["conservation"] = bool(cons["exact"]
                                 and not cons["in_flight"])
    detail["report_conservation"] = cons

    # strict every-check-ok, asserted at quiescence. A typed-covered
    # residue promotes from `degraded` to steady-state ok only once
    # its reading has been frozen past the auditor's stuck floor
    # (>= 2s after the last counter movement), so give the promotion
    # a bounded window instead of judging the first post-drain read.
    snap = None
    if srv.audit is not None:
        floor_s = getattr(srv.audit, "stuck_floor_s", 2.0)
        deadline = time.monotonic() + floor_s + 4.0
        while True:
            snap = srv.audit.evaluate()
            bad = [c for c in snap["checks"] if c["status"] != "ok"]
            if not bad or time.monotonic() > deadline:
                break
            time.sleep(0.3)
    if snap is None:
        gates["audit_ok"] = False
        ex = {"rate": 0.0, "pending": 1, "records": []}
    else:
        bad = [c for c in snap["checks"] if c["status"] != "ok"]
        gates["audit_ok"] = bool(snap["healthy"] and not bad)
        if bad or not snap["healthy"]:
            detail["audit_ok"] = {
                "healthy": snap["healthy"],
                "violated": [{"name": c["name"],
                              "status": c["status"],
                              "evidence": c.get("evidence")}
                             for c in bad]}
        ex = snap["explainability"]
    gates["explainability"] = bool(ex["rate"] == 1.0
                                   and not ex["pending"])
    kinds = _matched_kinds(ex)
    gates["fault_kinds"] = len(kinds) >= min_kinds
    detail["fault_kinds"] = sorted(kinds)
    detail["explainability"] = {
        "rate": ex["rate"],
        "matched": ex.get("matched", 0),
        "unexplained": ex.get("unexplained", 0),
        "pending": ex.get("pending", 0),
        # the injections still waiting for their evidence: a failed
        # gate has to name them
        "unmatched": [{k: r[k] for k in ("kind", "detail", "n",
                                         "expired")}
                      for r in ex.get("records", ())
                      if not r["matched"]]}

    # zero stale-generation serves: the watermark must never show
    # grants issued beyond the live generation, and the audited
    # grant_coherence invariant must read ok
    wm = srv.grants.watermark() if getattr(srv, "grants", None) \
        else None
    coherent = True
    if wm is not None:
        coherent = wm.get("issued_at_generation",
                          wm["generation"]) <= wm["generation"]
    if snap is not None:
        gc = next((c for c in snap["checks"]
                   if c["name"] == "grant_coherence"), None)
        coherent = coherent and (gc is None or gc["status"] == "ok")
    gates["no_stale_grants"] = bool(coherent)
    detail["grant_watermark"] = wm

    if snap is not None:
        pa = next((c for c in snap["checks"]
                   if c["name"] == "plane_agreement"), None)
        gates["plane_agreement"] = pa is None or \
            pa["status"] == "ok"
    else:
        gates["plane_agreement"] = False

    # client ledger <-> server front accounting
    sc = monitor.serving_counters()
    decoded = sc["requests_decoded"] \
        - base["serving"]["requests_decoded"]
    responded = sc["responses_sent"] \
        - base["serving"]["responses_sent"]
    oc = fleet_totals["outcomes"]
    wire = fleet_totals["wire_checks"]
    # cache-answered checks land in ok/denied but never crossed the
    # wire: only the wire-answered subset can match responses_sent
    answered = oc["ok"] + oc["denied"] \
        - fleet_totals.get("cache_hits", 0)
    rejected = oc["shed"] + oc["expired"] + oc["unavailable"] \
        + oc["error"]
    if restarted:
        # transport failures during the bounce never reached a front:
        # decoded is bounded by what the clients sent, and everything
        # decoded beyond the completed answers is a typed rejection
        ok_acct = (answered <= decoded <= wire
                   and responded >= answered
                   and decoded - responded <= rejected)
    else:
        ok_acct = (decoded == wire and responded == answered
                   and decoded - responded == rejected)
    gates["client_accounting"] = bool(ok_acct)
    detail["accounting"] = {
        "decoded_delta": decoded, "responded_delta": responded,
        "client_wire": wire, "client_answered": answered,
        "client_rejected": rejected,
        "client_outcomes": dict(oc),
        "restarted": restarted,
    }

    # routing conservation as the CLIENT saw it: no applied discovery
    # generation ever stopped serving a sidecar's own service
    gates["no_client_misroutes"] = oc.get("misrouted", 0) == 0

    gates["recovered"] = bool(recovery.get("recovered"))

    # violations after recovery: the counters must stay frozen over a
    # few more evaluations
    v0 = monitor.audit_counters()["violations"]
    for _ in range(max(int(settle_evals), 1)):
        time.sleep(settle_sleep_s)
        if srv.audit is not None:
            srv.audit.evaluate()
    v1 = monitor.audit_counters()["violations"]
    after = sum(v1[k] - v0.get(k, 0) for k in v1)
    gates["quiet_after_recovery"] = after == 0
    detail["violations_after_recovery"] = after

    return {
        "gates": gates,
        "all_ok": all(gates.values()),
        "detail": detail,
        "metrics": {
            "soak_recovery_s": recovery.get("soak_recovery_s"),
            "soak_explainability_rate": ex["rate"],
            "soak_violations_after_recovery": after,
            "soak_fault_kinds": sorted(kinds),
        },
    }
