"""Device programs launched per served Check batch: the program's
`mixer_device_programs_total{path}` (one count at each launch site,
FusedPlan.packed_check and packed_check_instep) over the count of the
span `dispatch.step`, which every served batch observes once at either
site, both since the window opened. 1.0 where the step, the
rule-telemetry fold and the packer are one jitted program; 4.0 where
they are launched apart. A program without the counter reads nothing."""
from istio_tpu.runtime import monitor

from spans import window_spans


def _programs():
    counters = getattr(monitor, "device_program_counters", None)
    return sum(counters().values()) if counters else None


def begin(ctx):
    programs = _programs()
    if programs is None:
        return None
    return programs, monitor.stage_baseline()


def read(ctx, base):
    if base is None:
        return None
    programs, spans_base = base
    seen = window_spans(spans_base) or {}
    batches = seen.get("dispatch.step", {}).get("count", 0)
    if not batches:
        return None
    return (_programs() - programs) / batches
