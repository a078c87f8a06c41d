"""Perf-rig integrity — the load generator cannot report a silent zero.

VERDICT r2 weak #1: a run once recorded zero served requests with rc=0
because the measurement window was anchored at parent wall-clock before
the spawned worker had even imported grpc. The rig now uses the
reference's attach pattern (mixer/pkg/perf/clientserver.go:30-90 —
clients register with the controller; load begins after attach), and
run_load raises PerfError instead of returning zeros.
"""
import pytest

from istio_tpu.attribute.bag import bag_from_mapping  # noqa: F401
from istio_tpu.runtime import MemStore, RuntimeServer, ServerArgs
from istio_tpu.testing import perf


def _tiny_store() -> MemStore:
    s = MemStore()
    s.set(("handler", "istio-system", "deny"), {
        "adapter": "denier", "params": {"status_code": 7}})
    s.set(("instance", "istio-system", "nothing"), {
        "template": "checknothing", "params": {}})
    s.set(("rule", "istio-system", "r0"), {
        "match": 'request.path.startsWith("/admin")',
        "actions": [{"handler": "deny", "instances": ["nothing"]}]})
    return s


@pytest.fixture(scope="module")
def aio_server():
    grpc = pytest.importorskip("grpc")  # noqa: F841
    from istio_tpu.api.grpc_server import MixerAioGrpcServer

    srv = RuntimeServer(_tiny_store(), ServerArgs(batch_window_s=0.001))
    g = MixerAioGrpcServer(srv)
    port = g.start()
    yield port
    g.stop()
    srv.close()


def test_run_load_measures_real_requests(aio_server):
    """Happy path: readiness barrier, then a window with traffic in it."""
    payloads = perf.make_check_payloads(
        [{"request.path": "/ok"}, {"request.path": "/admin/x"}])
    report = perf.run_load(f"127.0.0.1:{aio_server}", payloads,
                           n_record=200, n_procs=1, concurrency=4,
                           warmup_s=0.2)
    assert report.n_requests > 0
    assert report.n_requests + report.n_errors == 200
    assert report.checks_per_sec > 0
    assert report.p99_ms >= report.p50_ms > 0


def test_run_load_raises_when_attach_fails(aio_server):
    """A worker that cannot complete its first RPC aborts the run with
    PerfError — never a zero-valued PerfReport."""
    with pytest.raises(perf.PerfError):
        perf.run_load(f"127.0.0.1:{aio_server}",
                      [b"\xff\xff\xff\xff garbage protobuf"],
                      n_record=20, n_procs=1, concurrency=2,
                      warmup_s=0.1)
