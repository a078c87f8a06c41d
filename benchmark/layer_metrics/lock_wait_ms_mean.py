"""Mean wait of a ready thread for the interpreter lock: how late the
pump watch's heartbeat woke from its 50 ms sleeps
(`mixer_lock_wait_seconds` through monitor.pump_watch_snapshot), the
sum over the count of the heartbeats of the client's window (some 1 000
samples in 50 s). What a third pump would meet, and what every lent
wait of the two that are there pays to come back. Prints the window's
largest lateness beside it (`max_s`: a stall of the lock or of the
whole process). A program without the watch reads nothing.

The window is the client's (begin -> begin + its `duration_s`), not
begin -> read: the harness stops the profiler and reduces the trace
under the lock before readers run, for a minute and more in a large
cell, and a mean over those heartbeats too is not the server's. run.py
hands a reader neither the window's length at begin nor a call at its
end, so a thread of this reader's keeps a snapshot every PERIOD_S until
the client has reported, and read() takes the first one past the
window's end."""
import json
import threading
import time

from istio_tpu.runtime import monitor

PERIOD_S = 0.25


def begin(ctx):
    snapshot = getattr(monitor, "pump_watch_snapshot", None)
    if snapshot is None:
        return None
    base, kept = snapshot(), []

    def keep():
        while True:
            time.sleep(PERIOD_S)
            seen = snapshot(since=base)
            kept.append((seen["t"], seen["lock_wait"]))
            if ctx.client:      # the one after the client's report too
                return

    keeper = threading.Thread(target=keep, daemon=True)
    keeper.start()
    return base, keeper, kept


def read(ctx, token):
    if token is None:
        return None
    base, keeper, kept = token
    keeper.join()
    end = base["t"] + ctx.client["duration_s"]
    waited = next((w for t, w in kept if t >= end), kept[-1][1])
    if not waited["count"]:
        return None
    print(json.dumps({"phase": "lock_wait", "samples": waited["count"],
                      "max_s": waited["max_s"]}), flush=True)
    return 1e3 * waited["sum_s"] / waited["count"]
