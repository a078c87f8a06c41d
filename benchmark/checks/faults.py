"""The timed path broken underneath the harness, one fault at a time:
what `correct` has to catch. Each fault alters an answer where the
program produces it, either from the start (parity meets it) or only
once the window's client runs (parity has passed; what the client reads
in the replies has to catch it).

    python3 benchmark/checks/faults.py <fault> <when> --workload <cell>
        --seed <n> --seconds <s> [--smoke]

runs benchmark/run.py's own `main` under the fault: the control of
`correct`, on the chip at the cell's own size. Its last line must read
`"correct": false`. `checks/test_quota_grant.py` keeps the same faults
at smoke size.
"""
from __future__ import annotations

import contextlib
import dataclasses
import sys
import threading
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
for p in (str(BENCH.parent), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

ARMED = threading.Event()
WHEN = ("from_start", "in_window")


def _grant_zero(patch):
    """The pool grants nothing: every allocation resolves to 0."""
    from istio_tpu.runtime.device_quota import QuotaFuture

    plain = QuotaFuture.set

    def set_(self, value):
        if ARMED.is_set():
            value = dataclasses.replace(value, granted_amount=0)
        plain(self, value)

    patch(QuotaFuture, "set", set_)


def _omit_quotas(patch):
    """The server answers a quota row with no `quotas` entry."""
    from istio_tpu.api.grpc_server import MixerGrpcServer

    plain = MixerGrpcServer._check_response

    def respond(self, request, bag, result, quotas=None, **kw):
        if ARMED.is_set() and quotas:
            quotas = []
        return plain(self, request, bag, result, quotas=quotas, **kw)

    patch(MixerGrpcServer, "_check_response", respond)


def _alter_status(patch):
    """A quota row that the snapshot allows is answered
    PERMISSION_DENIED (and so without its grant)."""
    from istio_tpu.api.grpc_server import MixerGrpcServer

    plain = MixerGrpcServer._check_response

    def respond(self, request, bag, result, quotas=None, **kw):
        reply = plain(self, request, bag, result, quotas=quotas, **kw)
        if ARMED.is_set() and quotas and result.status_code == 0:
            reply.precondition.status.code = 7
            reply.quotas.clear()
        return reply

    patch(MixerGrpcServer, "_check_response", respond)


def _no_consume(patch):
    """The pool grants as it should and keeps no count: the counter
    table on the device is what it was before each flush (a flush whose
    device trip was dropped, or a kernel that skips the update)."""
    import jax.numpy as jnp

    from istio_tpu.runtime.device_quota import DeviceQuotaPool

    plain = DeviceQuotaPool._flush

    def flush(self, batch):
        if not ARMED.is_set():
            return plain(self, batch)
        with self._counts_lock:     # a copy: the flush donates the table
            before = jnp.array(self.counts, copy=True)
        plain(self, batch)
        with self._counts_lock:
            self.counts = before

    patch(DeviceQuotaPool, "_flush", flush)


FAULTS = {"grant_zero": _grant_zero, "omit_quotas": _omit_quotas,
          "alter_status": _alter_status, "no_consume": _no_consume}


@contextlib.contextmanager
def installed(fault: str, when: str, run):
    """`fault` under `run` (the benchmark/run.py module) until the
    block ends; `in_window` arms it as run_window starts."""
    undo = []

    def patch(owner, name, value):
        undo.append((owner, name, getattr(owner, name)))
        setattr(owner, name, value)

    FAULTS[fault](patch)
    if when == "from_start":
        ARMED.set()
    else:
        window = run.run_window

        def armed_window(*args, **kw):
            ARMED.set()
            return window(*args, **kw)

        patch(run, "run_window", armed_window)
    try:
        yield
    finally:
        ARMED.clear()
        for owner, name, value in reversed(undo):
            setattr(owner, name, value)


if __name__ == "__main__":
    import run

    fault, when, *rest = sys.argv[1:]
    if fault not in FAULTS or when not in WHEN:
        sys.exit(__doc__)
    with installed(fault, when, run):
        sys.exit(run.main(rest))
