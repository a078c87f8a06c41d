"""Automata one row is scanned against, summed over the scanned
subjects: the gauge mixer_dfa_candidates_max{subject}, set at plan
build (monitor.note_dfa_banks): a host's blocks under the candidate
tier (plus the automata no host guards), the whole bank under the
others. A program without the gauge, or a snapshot with no regex, is
not read."""
from istio_tpu.runtime import monitor


def read(ctx, _):
    gauge = getattr(monitor, "DFA_CANDIDATES_MAX", None)
    if gauge is None:
        return None
    total = sum(gauge.value(**labels) for labels in gauge.label_sets())
    return total or None
