"""Secure serving plane units (istio_tpu/secure): the PkiBackend seam,
WorkloadIdentity lifecycle + executor maintenance-lane registration,
ServingCerts hot rotation, SPIFFE extraction, the identity axis of the
grant plane, the client-side principal cache fold, and the permissive
and native-TLS-lane front postures the strict-mode smoke
(scripts/mtls_smoke.py) doesn't cover."""
from __future__ import annotations

import time
import types

import grpc
import pytest

from istio_tpu.secure.backend import available_backends

if not available_backends():
    pytest.skip("secure plane needs a PKI backend (cryptography or "
                "the openssl CLI)", allow_module_level=True)

from istio_tpu.api.client import MixerClient
from istio_tpu.api.grpc_server import MixerGrpcServer
from istio_tpu.runtime import MemStore, RuntimeServer, ServerArgs
from istio_tpu.runtime import monitor
from istio_tpu.secure.identity import WorkloadIdentity
from istio_tpu.secure.mtls import ServingCerts, spiffe_identity_from_pem
from istio_tpu.security import IstioCA, pki, spiffe_id

WEB = spiffe_id("default", "web")


@pytest.fixture(scope="module")
def ca():
    return IstioCA.new_self_signed({})


class InProcessCA:
    """CAClient-shaped duck signing straight through an IstioCA — the
    WorkloadIdentity units don't need the gRPC hop."""

    def __init__(self, ca, fail: bool = False, reject: bool = False):
        self.ca = ca
        self.fail = fail
        self.reject = reject
        self.calls = 0

    def sign_csr(self, csr_pem, credential=b"", credential_type="",
                 ttl_minutes=0):
        self.calls += 1
        if self.fail:
            raise ConnectionError("CA down")
        if self.reject:
            return types.SimpleNamespace(
                is_approved=False, signed_cert=b"", cert_chain=b"",
                status_message="authorization failed")
        import datetime
        cert = self.ca.sign(csr_pem, datetime.timedelta(
            minutes=ttl_minutes) if ttl_minutes else None)
        return types.SimpleNamespace(
            is_approved=True, signed_cert=cert,
            cert_chain=self.ca.get_root_certificate(),
            status_message="")


def _serving(ca, dns=("mixer.local",)):
    key = pki.generate_key()
    cert = ca.sign(pki.generate_csr(
        key, spiffe_id("istio-system", "mixer"), dns_names=dns))
    return ServingCerts(pki.key_to_pem(key), cert,
                        ca.get_root_certificate())


# -- backend seam ------------------------------------------------------

def test_backend_seam_reports_a_live_backend():
    names = available_backends()
    assert names
    assert set(names) <= {"cryptography", "openssl"}


def test_backend_pem_interops_with_tls_stack(ca):
    """The active backend's PEM output must parse back through the
    seam (subject, SANs, TTL) — the byte-compatibility contract."""
    key = pki.generate_key()
    cert = ca.sign(pki.generate_csr(key, WEB, dns_names=("web.local",)),
                   __import__("datetime").timedelta(minutes=7))
    assert pki.san_uris(cert) == [WEB]
    assert "web.local" in pki.san_dns(cert)
    remaining = (pki.not_after(cert)
                 - __import__("datetime").datetime.now(
                     __import__("datetime").timezone.utc)
                 ).total_seconds()
    assert 0 < remaining < 10 * 60


# -- ServingCerts ------------------------------------------------------

def test_serving_certs_rotation_bumps_generation(ca):
    certs = _serving(ca)
    assert certs.generation == 1
    key2 = pki.generate_key()
    cert2 = ca.sign(pki.generate_csr(
        key2, spiffe_id("istio-system", "mixer"),
        dns_names=("mixer.local",)))
    gen = certs.rotate(pki.key_to_pem(key2), cert2)
    assert gen == 2
    k, c, r, g = certs.bundle()
    assert (k, c, g) == (pki.key_to_pem(key2), cert2, 2)
    assert r == ca.get_root_certificate()    # root carried over


def test_serving_certs_context_memoized_per_generation(ca):
    certs = _serving(ca)
    c1 = certs.ssl_server_context()
    assert certs.ssl_server_context() is c1
    assert certs.ssl_server_context(require_client_cert=True) is not c1
    key2 = pki.generate_key()
    certs.rotate(pki.key_to_pem(key2), ca.sign(pki.generate_csr(
        key2, spiffe_id("istio-system", "mixer"))))
    assert certs.ssl_server_context() is not c1


def test_spiffe_identity_extraction(ca):
    key = pki.generate_key()
    cert = ca.sign(pki.generate_csr(key, WEB))
    assert spiffe_identity_from_pem(cert) == WEB
    bare = ca.sign(pki.generate_csr(pki.generate_key(), None, org="x"))
    assert spiffe_identity_from_pem(bare) is None


# -- WorkloadIdentity lifecycle ---------------------------------------

def test_identity_issue_and_rotate(ca):
    seen = []
    wi = WorkloadIdentity(InProcessCA(ca), WEB, ttl_minutes=5,
                          on_rotate=(seen.append,))
    assert wi.due()                      # no bundle yet
    key_pem, cert_pem, root_pem = wi.ensure()
    assert pki.san_uris(cert_pem) == [WEB]
    assert root_pem == ca.get_root_certificate()
    assert wi.generation == 1 and not wi.due()
    assert wi.ensure() == (key_pem, cert_pem, root_pem)  # cached
    wi.rotate()
    assert wi.generation == 2 and wi.rotations == 1
    assert len(seen) == 2 and seen[1][1] != cert_pem
    stats = wi.stats()
    assert stats["identity"] == WEB and stats["failures"] == 0
    assert stats["remaining_ttl_s"] > 0


def test_identity_failure_paths_are_counted(ca):
    base = monitor.identity_counters()["events"]["issue"]["failed"]
    wi = WorkloadIdentity(InProcessCA(ca, fail=True), WEB)
    with pytest.raises(ConnectionError):
        wi.ensure()
    assert wi.failures == 1 and "ConnectionError" in wi.last_error
    rej = WorkloadIdentity(InProcessCA(ca, reject=True), WEB)
    with pytest.raises(RuntimeError, match="CSR rejected"):
        rej.ensure()
    now = monitor.identity_counters()["events"]["issue"]["failed"]
    assert now >= base + 2


def test_identity_refresh_rotates_when_due(ca):
    client = InProcessCA(ca)
    # rotation_fraction=1.0: due the instant a bundle exists — every
    # maintenance tick rotates (the soak cadence trick)
    wi = WorkloadIdentity(client, WEB, ttl_minutes=5,
                          rotation_fraction=1.0)
    wi.refresh()                         # no bundle -> issue
    assert wi.generation == 1 and wi.rotations == 0
    wi.refresh()                         # due -> rotate
    assert wi.generation == 2 and wi.rotations == 1
    calm = WorkloadIdentity(client, WEB, ttl_minutes=5,
                            rotation_fraction=0.1)
    calm.refresh()
    calm.refresh()                       # fresh cert: not due
    assert calm.generation == 1


def test_identity_rides_executor_maintenance_lane(ca):
    srv = RuntimeServer(MemStore(), ServerArgs(batch_window_s=0.001))
    try:
        assert srv.executor is not None
        wi = WorkloadIdentity(InProcessCA(ca), WEB, ttl_minutes=5,
                              rotation_fraction=1.0,
                              refresh_interval_s=0.05)
        srv.executor.register_refreshable("workload_identity", wi)
        deadline = time.time() + 10
        while wi.generation < 2 and time.time() < deadline:
            time.sleep(0.05)
        assert wi.generation >= 2        # issued AND rotated by lane
        # a config republish rebuilds the registry; the persistent
        # refreshable must survive it
        srv.executor.register_refreshables({})
        gen = wi.generation
        deadline = time.time() + 10
        while wi.generation == gen and time.time() < deadline:
            time.sleep(0.05)
        assert wi.generation > gen
    finally:
        srv.close()


# -- identity axis of the grant plane ---------------------------------

def test_identity_grant_fold():
    srv = RuntimeServer(MemStore(), ServerArgs(batch_window_s=0.001,
                                               check_grants=True))
    try:
        g = srv.grants
        ttl, uses = g.identity_grant(WEB)
        assert (ttl, uses) == (g.ttl_cap_s, g.use_cap)   # never rotated
        g.on_identity_rotate(WEB)
        ttl, _ = g.identity_grant(WEB)
        assert ttl <= g.ttl_floor_s + 0.5
        st = g.stats()
        assert st["identity_revocations"] == 1
        assert st["identities_tracked"] == 1
    finally:
        srv.close()


def test_client_signature_folds_principal(ca):
    from istio_tpu.api import mixer_pb2 as pb
    key = pki.generate_key()
    cert = ca.sign(pki.generate_csr(key, WEB))
    cl = MixerClient("127.0.0.1:1", root_cert_pem=b"-----BEGIN "
                     b"CERTIFICATE-----\n-----END CERTIFICATE-----\n",
                     key_pem=pki.key_to_pem(key), cert_pem=cert)
    try:
        assert cl._identity == WEB
        sig = cl._signature(pb.ReferencedAttributes(), {})
        assert sig[0] == ("__peer_identity__", None, WEB)
        cl._cache[("x",)] = ["entry"]
        cl.set_identity(WEB)             # same principal: cache kept
        assert cl._cache
        cl.set_identity(spiffe_id("default", "other"))
        assert not cl._cache             # principal changed: dropped
        assert cl._signature(pb.ReferencedAttributes(), {})[0][2] \
            == spiffe_id("default", "other")
    finally:
        cl.close()


# -- front postures the strict smoke doesn't cover --------------------

def test_permissive_front_encrypts_without_identity(ca):
    """Permissive: TLS encryption, client certs never requested, and
    therefore NO identity attributes are injected (connection.mtls
    stays honest — see secure/mtls.py docstring)."""
    certs = _serving(ca)
    store = MemStore()
    store.set(("handler", "istio-system", "denyall"), {
        "adapter": "denier", "params": {"status_message": "rbac"}})
    store.set(("instance", "istio-system", "nothing"), {
        "template": "checknothing", "params": {}})
    store.set(("rule", "istio-system", "deny-identified"), {
        "match": '(source.user | "") != ""',
        "actions": [{"handler": "denyall",
                     "instances": ["nothing"]}]})
    srv = RuntimeServer(store, ServerArgs(batch_window_s=0.001))
    front = MixerGrpcServer(srv, tls=certs, mtls_mode="permissive")
    cl = None
    try:
        base_auth = monitor.identity_counters()[
            "authenticated_checks_total"]
        port = front.start()
        cl = MixerClient(f"127.0.0.1:{port}",
                         enable_check_cache=False,
                         root_cert_pem=ca.get_root_certificate(),
                         server_name="mixer.local")
        resp = cl.check({"destination.service": "a.default.svc"})
        # no injected source.user -> the deny-identified rule is idle
        assert resp.precondition.status.code == 0
        assert monitor.identity_counters()[
            "authenticated_checks_total"] == base_auth
    finally:
        if cl is not None:
            cl.close()
        front.stop()
        srv.close()


def test_strict_front_requires_serving_certs():
    srv = RuntimeServer(MemStore(), ServerArgs(batch_window_s=0.001))
    try:
        with pytest.raises(ValueError, match="certs"):
            MixerGrpcServer(srv, tls=None, mtls_mode="strict")
        with pytest.raises(ValueError, match="mtls"):
            MixerGrpcServer(srv, tls=None, mtls_mode="bogus")
    finally:
        srv.close()


def test_native_front_tls_lane(ca):
    """The native h2 front serves through the stdlib-ssl terminating
    lane: strict handshakes verify the workload cert, cert-less peers
    never reach the pump, and a rotation applies to new accepts."""
    certs = _serving(ca)
    from istio_tpu.api.native_server import NativeMixerServer
    srv = RuntimeServer(MemStore(), ServerArgs(batch_window_s=0.001))
    native = NativeMixerServer(srv, tls=certs, mtls_mode="strict")
    cl = anon = None
    try:
        native.start()
        assert native.secure_port
        key = pki.generate_key()
        cert = ca.sign(pki.generate_csr(key, WEB))
        cl = MixerClient(f"127.0.0.1:{native.secure_port}",
                         enable_check_cache=False,
                         root_cert_pem=ca.get_root_certificate(),
                         key_pem=pki.key_to_pem(key), cert_pem=cert,
                         server_name="mixer.local")
        resp = cl.check({"destination.service": "a.default.svc"})
        assert resp.precondition.status.code == 0
        anon = MixerClient(f"127.0.0.1:{native.secure_port}",
                           enable_check_cache=False,
                           root_cert_pem=ca.get_root_certificate(),
                           server_name="mixer.local")
        with pytest.raises(grpc.RpcError) as exc:
            anon.check({"destination.service": "a.default.svc"})
        assert exc.value.code() == grpc.StatusCode.UNAVAILABLE
        # the lane's thread counts the refusal a moment after the
        # client has seen it
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and \
                not native.tls_lane_stats()["handshake_failures"]:
            time.sleep(0.01)
        assert native.tls_lane_stats()["handshake_failures"] >= 1
    finally:
        for c in (cl, anon):
            if c is not None:
                c.close()
        native.stop()
        srv.close()
