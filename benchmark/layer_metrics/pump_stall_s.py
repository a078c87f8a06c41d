"""Seconds a pump sat in one top-level span past the stall threshold
(monitor.STALL_S, 0.2 s), whole residences, summed over the ten spans:
the program's `mixer_pump_stall_seconds{span}` through
monitor.pump_watch_snapshot, over the WHOLE window (begin -> read), not
the traced seconds. 0.0, not nothing, in a run without a stall. Under
`take_wait` it is the seconds rows waited while a pump asked for them,
or the client was silent (cause `client`; client_silent_s says how much
of it that was), never an idle pump's residence. Also prints one
progress line with every `pump.stall` event since begin: span, pump,
seconds, cause, the heartbeat's lateness, the C++ front's three gaps,
what moved beside it, and each thread's innermost five frames. An event
with no span (the heartbeat woke late and no pump was in a span old
enough to say so: the whole process stood still, `cause` process, or a
thread held the lock) adds nothing to the number and is the one to read
first beside a client `max_ms` of seconds. One that ended more than a
second after the client's window is marked `after_the_client`: the
harness stops and reduces the trace under the lock then. A program
without the watch reads nothing."""
import json

from istio_tpu.runtime import monitor

SHOWN = ("cause", "span", "nested", "pump", "seconds", "lock_late_s",
         "starved_s", "silent_s", "io_s", "gc_full_s", "gc_young_s",
         "device_retries", "cache_hits", "cache_misses", "others", "n")


def begin(ctx):
    snapshot = getattr(monitor, "pump_watch_snapshot", None)
    return snapshot() if snapshot else None


def shown(event: dict) -> dict:
    out = {k: event[k] for k in SHOWN if k in event}
    stacks = sorted(event.get("stacks", []),       # the pumps first
                    key=lambda s: "pump" not in s["thread"])
    out["stacks"] = [{"thread": s["thread"], "frames": s["frames"][:5]}
                     for s in stacks[:8]]
    return out


def read(ctx, base):
    if base is None:
        return None
    seen = monitor.pump_watch_snapshot(since=base)
    end_ns = (base["t"] + ctx.client["duration_s"] + 1.0) * 1e9
    print(json.dumps({"phase": "stalls", "events": [
        shown(e) | {"after_the_client": e.get("t1_ns", 0) > end_ns}
        for e in seen["events"]]}), flush=True)
    return float(sum(v["sum_s"] for v in seen["stalls"].values()))
