"""Resident device megabytes of the snapshot's DFA banks, all subjects:
the gauge mixer_dfa_bank_bytes{subject}, set at plan build
(monitor.note_dfa_banks). A program without the gauge, or a snapshot
with no regex, is not read."""
from istio_tpu.runtime import monitor


def read(ctx, _):
    gauge = getattr(monitor, "DFA_BANK_BYTES", None)
    if gauge is None:
        return None
    total = sum(gauge.value(**labels) for labels in gauge.label_sets())
    return total / 1e6 if total else None
