"""Whole-mesh chaos soak (ROADMAP item 5's open leg).

Three pieces, composed by scripts/soak_smoke.py:

  * fleet.FleetSimulator — N simulated sidecars running the full
    client lifecycle concurrently (discovery watch + config-version
    apply, Check/Report/quota traffic through the REAL fronts with
    client check-caches, closed-loop pacing) with a per-sidecar typed
    outcome ledger, so conservation is checkable from the client side;
  * storm.StormChoreographer — a seeded, deterministic schedule of
    control-side events (churn publishes, canary vetoes, adapter
    wedges, device faults, quota-backend stalls, discovery push
    delays, grant revocation storms, a mid-soak restart) replayed
    against the live server in typed phases warmup → storm → recovery,
    every injection registered in the audit plane's InjectionLedger;
  * gates — the recovery gates, evaluated from existing surfaces only:
    exact report conservation, audit all-ok within a bound
    (soak_recovery_s), explainability rate 1.0, zero stale-generation
    serves, plane agreement, and the client-ledger ↔ mixer_* counter
    accounting identity.
"""
from istio_tpu.soak.fleet import (FleetSimulator, SidecarLedger,
                                  OUTCOMES)
from istio_tpu.soak.storm import (StormChoreographer, StormEvent,
                                  make_schedule, clear_chaos,
                                  schedule_signature, PHASES)
from istio_tpu.soak.gates import (snapshot_baselines, wait_quiesce,
                                  wait_recovery, evaluate_gates)
from istio_tpu.soak.harness import SoakConfig, SoakHarness, run_soak

__all__ = [
    "FleetSimulator", "SidecarLedger", "OUTCOMES",
    "StormChoreographer", "StormEvent", "make_schedule",
    "clear_chaos", "schedule_signature", "PHASES",
    "snapshot_baselines", "wait_quiesce", "wait_recovery",
    "evaluate_gates", "SoakConfig", "SoakHarness", "run_soak",
]
