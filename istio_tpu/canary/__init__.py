"""Config canary — record live Check() traffic, shadow-replay
prospective snapshots on device, gate the swap.

The shadow-deployment / canary-analysis pattern from production
inference stacks applied to the policy plane: PR 3's analyzer rejects
config that is wrong *by construction*, but a statically clean
snapshot can still flip decisions for real users (a tightened match
clause, a reordered ALLOW/DENY overlap the analyzer only WARNs on).
Before the Controller's atomic publish swaps a rebuilt snapshot in,
the candidate `FusedPlan` is validated against RECORDED live traffic
on the same hardware:

  record  — `TrafficRecorder` (recorder.py): a lock-light sampling
            ring buffer tapped at the dispatcher boundary captures
            recent Check() traffic as compressed attribute bags (the
            rulestats exemplar compression) plus the served decision.
  replay  — `replay_entries` (replay.py): the corpus batch-replays
            through the candidate plan in observe-off mode (no
            rulestats / stage-metric / chaos pollution) on device.
  diff    — `diff_decisions` (differ.py): per-request divergence
            classification (status flip, precondition TTL/use-count
            change, quota delta) aggregated per rule, with reservoir
            exemplars (bag + trace id) and oracle re-confirmation.
  gate    — `ConfigCanary` (gate.py): --canary={off,warn,gate}; `gate`
            vetoes the publish (typed `CanaryRejected`, old dispatcher
            keeps serving), `warn` publishes but records the report.

Surfaces: /debug/canary (introspect), `mixer_canary_*` metric
families, kube/admission.register_canary_admission and the `canary`
CLI subcommand.
"""
from istio_tpu.canary.differ import (CanaryReport, Divergence,
                                     diff_decisions, oracle_decision)
from istio_tpu.canary.gate import (CanaryConfig, CanaryRejected,
                                   ConfigCanary)
from istio_tpu.canary.recorder import (CanaryEntry, TrafficRecorder,
                                       entry_from_json, entry_to_json,
                                       load_corpus, save_corpus)
from istio_tpu.canary.replay import ReplayResult, replay_entries

__all__ = [
    "CanaryConfig", "CanaryEntry", "CanaryRejected", "CanaryReport",
    "ConfigCanary", "Divergence", "ReplayResult", "TrafficRecorder",
    "diff_decisions", "entry_from_json", "entry_to_json",
    "load_corpus", "oracle_decision", "replay_entries", "save_corpus",
]
