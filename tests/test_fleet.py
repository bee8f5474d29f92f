"""Fleet serving (gatekeeper_tpu/fleet/, docs/fleet.md, ISSUE 7).

Covers the single-role App contract (a webhook-only replica runs no
audit manager, no snapshot writer, no status controllers — the ISSUE's
acceptance assertion), the stdlib front door (round-robin and
least-inflight choice, dead-backend failover, explicit 502 when every
backend is down, /fleetz stats), the load-adaptive micro-batcher's
controller (equilibrium target, deadline, idle reset, dormancy without a
calibration, exported gauges), the aux-server idempotent starts, and
replica-identity stamping across spans / metrics / SLO payloads.
"""

import json
import threading
import time
import urllib.request

import pytest

from gatekeeper_tpu import operations as ops_mod
from gatekeeper_tpu.fleet import EventFrontDoor
from gatekeeper_tpu.fleet.roster import Roster
from gatekeeper_tpu.kube.inmem import InMemoryKube
from gatekeeper_tpu.util import replica_id, set_replica_id
from gatekeeper_tpu.webhook import MicroBatcher
from tests.wirestub import StubWire, get, post


@pytest.fixture(autouse=True)
def _clear_replica_id():
    yield
    set_replica_id("")


# ---- operations role helpers ------------------------------------------------


class TestOperationsRoles:
    def test_default_is_every_operation(self):
        ops = ops_mod.Operations()
        assert ops.assigned_set() == set(ops_mod.ALL_OPERATIONS)
        assert not ops.explicitly_assigned()
        assert not ops.is_only(ops_mod.WEBHOOK)

    def test_single_role(self):
        ops = ops_mod.Operations([ops_mod.WEBHOOK])
        assert ops.assigned_set() == {ops_mod.WEBHOOK}
        assert ops.explicitly_assigned()
        assert ops.is_only(ops_mod.WEBHOOK)
        assert not ops.is_only(ops_mod.AUDIT)

    def test_multi_role_is_not_only(self):
        ops = ops_mod.Operations([ops_mod.WEBHOOK, ops_mod.AUDIT])
        assert not ops.is_only(ops_mod.WEBHOOK)
        assert ops.is_assigned(ops_mod.WEBHOOK)


# ---- single-role App wiring (the fleet replica's contract) ------------------


def _make_app(tmp_path, *ops):
    from gatekeeper_tpu.main import App, build_parser

    flags = [
        "--driver", "interp",
        "--port", "0",
        "--prometheus-port", "0",
        "--health-addr", ":0",
        "--disable-cert-rotation",
        "--snapshot-dir", str(tmp_path / "snap"),
    ]
    for op in ops:
        flags += ["--operation", op]
    return App(build_parser().parse_args(flags), kube=InMemoryKube())


class TestSingleRoleApp:
    def test_webhook_only_runs_no_audit_no_snapshotter_no_status(
        self, tmp_path,
    ):
        """The ISSUE 7 acceptance assertion: a webhook-only replica must
        not run an audit manager, must not ARM the snapshot writer (it is
        a read-mostly consumer of the shared dir), and must not run the
        status writers."""
        app = _make_app(tmp_path, ops_mod.WEBHOOK)
        app.start()
        try:
            assert app.audit_manager is None
            assert app.snapshotter is None
            assert not hasattr(app.manager, "constraint_status")
            assert not hasattr(app.manager, "template_status")
            assert app.micro_batcher is not None
            assert app.webhook_server is not None
        finally:
            app.stop()

    def test_audit_only_arms_snapshotter_and_no_webhook(self, tmp_path):
        app = _make_app(tmp_path, ops_mod.AUDIT)
        app.start()
        try:
            assert app.audit_manager is not None
            assert app.snapshotter is not None
            assert app.micro_batcher is None
            assert app.webhook_server is None
        finally:
            app.stop()


# ---- front door -------------------------------------------------------------


class TestFrontDoor:
    def test_round_robin_rotates(self):
        a, b = StubWire(name="a"), StubWire(name="b")
        door = EventFrontDoor(
            [a.backend(), b.backend()], policy="round_robin",
        ).start()
        try:
            replicas = []
            for _ in range(6):
                _st, hd, data = post(door.port)
                assert json.loads(data)["served_by"] in ("a", "b")
                replicas.append(hd["X-GK-Replica"])
            assert replicas.count("a") == 3
            assert replicas.count("b") == 3
        finally:
            door.stop()
            a.stop()
            b.stop()

    def test_least_inflight_prefers_idle_backend(self):
        slow, fast = StubWire(name="slow", delay_s=0.25), \
            StubWire(name="fast")
        door = EventFrontDoor(
            [slow.backend(), fast.backend()], policy="least_inflight",
        ).start()
        try:
            out = []
            lock = threading.Lock()

            def one():
                _st, hd, _d = post(door.port)
                with lock:
                    out.append(hd["X-GK-Replica"])

            threads = [threading.Thread(target=one) for _ in range(10)]
            for t in threads:
                t.start()
                time.sleep(0.02)  # arrivals overlap the slow service time
            for t in threads:
                t.join(timeout=30)
            assert len(out) == 10
            # while the slow backend holds a request in flight, new
            # arrivals must land on the idle one
            assert out.count("fast") > out.count("slow")
        finally:
            door.stop()
            slow.stop()
            fast.stop()

    def test_dead_backend_fails_over(self):
        dead, live = StubWire(name="dead"), StubWire(name="live")
        dead.stop()  # port is now refused
        door = EventFrontDoor(
            [dead.backend(), live.backend()], policy="round_robin",
        ).start()
        try:
            for _ in range(4):
                st, hd, _data = post(door.port)
                assert st == 200
                assert hd["X-GK-Replica"] == "live"
            stats = {
                b["replica_id"]: b for b in door.stats()["backends"]
            }
            assert stats["dead"]["errors"] >= 1
            assert stats["live"]["served"] == 4
        finally:
            door.stop()
            live.stop()

    def test_healthz_liveness_is_recent_not_sticky(self):
        """A backend that once served but now fails every request is
        dead: /healthz must go 503 once every backend's error streak
        passes LIVE_ERROR_STREAK — a sticky served counter would keep
        answering 200 while every POST returns 502."""
        b = StubWire(name="b0")
        door = EventFrontDoor([b.backend()]).start()
        try:
            assert post(door.port)[0] == 200  # served > 0
            assert get(door.port, "/healthz")[0] == 200
            b.stop()  # backend dies after serving
            for _ in range(Roster.LIVE_ERROR_STREAK):
                assert post(door.port)[0] == 502
            assert get(door.port, "/healthz")[0] == 503
        finally:
            door.stop()

    def test_fleetz_and_unknown_path(self):
        a = StubWire(name="a")
        door = EventFrontDoor([("127.0.0.1", a.port)]).start()
        try:
            st, body = get(door.port, "/fleetz")
            stats = json.loads(body)
            assert st == 200
            assert stats["policy"] == "least_inflight"
            assert len(stats["backends"]) == 1
            assert get(door.port, "/nope")[0] == 404
        finally:
            door.stop()
            a.stop()

    def test_rejects_unknown_policy_and_empty_backends(self):
        with pytest.raises(ValueError):
            EventFrontDoor([("127.0.0.1", 1)], policy="weighted")
        with pytest.raises(ValueError):
            EventFrontDoor([])


# ---- load-adaptive micro-batcher -------------------------------------------


class _ModelDriver:
    """Affine service model: T(B) = floor + B*per_review (ms)."""

    def __init__(self, floor_ms=0.2, per_review_ms=0.05):
        self.floor_ms = floor_ms
        self.per_review_ms = per_review_ms
        self.loads = []

    def predicted_batch_ms(self, n):
        return self.floor_ms + n * self.per_review_ms

    def set_offered_load(self, rps):
        self.loads.append(rps)


class _ModelClient:
    def __init__(self, driver=None):
        self.driver = driver if driver is not None else _ModelDriver()

    def review_batch(self, objs, tracing=False):
        return [None] * len(objs)


def _equilibrium(driver, lam, max_batch=256):
    """The fixed point B = λ·T(B) the controller iterates toward."""
    lam_pms = lam / 1e3
    b = 1.0
    for _ in range(4):
        t = driver.predicted_batch_ms(max(int(b), 1))
        nb = min(max(lam_pms * t, 1.0), float(max_batch))
        if abs(nb - b) < 0.5:
            return nb
        b = nb
    return b


class TestAdaptiveBatcher:
    def _batcher(self, **kw):
        return MicroBatcher(_ModelClient(), window_s=0.002, **kw)

    def test_low_load_targets_immediate_dispatch(self):
        mb = self._batcher()
        try:
            with mb._rate_lock:
                mb._load_rps = 50.0  # sparse traffic
            target, deadline = mb._adapt()
            assert target == 1
            assert deadline == 0.0
        finally:
            mb.stop()

    def test_high_load_grows_target_and_sets_deadline(self):
        mb = self._batcher()
        drv = mb._client.driver
        try:
            with mb._rate_lock:
                mb._load_rps = 20000.0
            target, deadline = mb._adapt()
            want = _equilibrium(drv, 20000.0)
            assert target == max(int(round(want)), 1) and target > 1
            # deadline = time for λ to deliver the target, capped
            assert deadline == pytest.approx(
                min(target / 20000.0, mb.max_deadline_s)
            )
            # λ pushed to the driver so routing is load-aware
            assert drv.loads[-1] == 20000.0
        finally:
            mb.stop()

    def test_extreme_load_caps_at_max_batch_and_deadline(self):
        mb = self._batcher(max_deadline_s=0.010)
        try:
            with mb._rate_lock:
                mb._load_rps = 1e9
            target, deadline = mb._adapt()
            assert target == mb.max_batch
            assert deadline <= 0.010
        finally:
            mb.stop()

    def test_static_mode_never_adapts(self):
        mb = self._batcher(adaptive=False)
        try:
            with mb._rate_lock:
                mb._load_rps = 1e6
            assert mb._adapt() == (1, 0.0)
            assert mb._client.driver.loads == []
        finally:
            mb.stop()

    def test_no_calibration_stays_dormant(self):
        class _Bare:
            pass

        class _BareClient:
            driver = _Bare()

            def review_batch(self, objs, tracing=False):
                return [None] * len(objs)

        mb = MicroBatcher(_BareClient())
        try:
            with mb._rate_lock:
                mb._load_rps = 1e6
            assert mb._adapt() == (1, 0.0)
        finally:
            mb.stop()

    def test_model_failure_never_stalls_dispatch(self):
        class _Boom(_ModelDriver):
            def predicted_batch_ms(self, n):
                raise RuntimeError("model broke")

        mb = MicroBatcher(_ModelClient(_Boom()))
        try:
            with mb._rate_lock:
                mb._load_rps = 1e6
            assert mb._adapt() == (1, 0.0)
        finally:
            mb.stop()

    def test_idle_gap_resets_rate_outright(self):
        """A burst minutes ago must not tax today's lone request: one
        bucket roll across a long idle gap adopts the gap's (near-zero)
        rate instead of EWMA-halving the stale burst rate."""
        mb = self._batcher()
        try:
            with mb._rate_lock:
                mb._load_rps = 50000.0  # stale burst
                mb._arrivals = 1        # the lone request after the lull
                mb._rate_t0 = time.monotonic() - (mb.IDLE_RESET_S + 1.0)
            lam = mb.offered_load_rps()
            assert lam < 1.0
            target, deadline = mb._adapt()
            assert (target, deadline) == (1, 0.0)
        finally:
            mb.stop()

    def test_short_bucket_blends_ewma(self):
        mb = self._batcher()
        try:
            with mb._rate_lock:
                mb._load_rps = 1000.0
                mb._arrivals = 500
                mb._rate_t0 = time.monotonic() - 0.5  # ~1000 rps observed
            lam = mb.offered_load_rps()
            # blended, not replaced: stays in the same decade
            assert 900.0 < lam < 1100.0
        finally:
            mb.stop()

    def test_adaptive_window_clamped_to_member_deadline(self, monkeypatch):
        """A deadline-budgeted request must never be held past its own
        budget by the adaptive accumulation window and then refused: the
        window clamps to the earliest queued deadline minus a dispatch
        margin, so the request dispatches (and succeeds) in budget."""
        from gatekeeper_tpu import deadline as dl

        mb = self._batcher()
        try:
            # force a long adaptive window the single request can't fill
            monkeypatch.setattr(mb, "_adapt", lambda: (64, 10.0))
            token = dl.push(0.25)  # 250ms budget << the 10s window
            try:
                t0 = time.monotonic()
                mb.review({"kind": "Pod"})  # must NOT DeadlineExceeded
                waited = time.monotonic() - t0
            finally:
                dl.pop(token)
            # dispatched at the budget clamp, not the adaptive window
            assert waited < 1.0
        finally:
            mb.stop()

    def test_stop_clears_the_driver_load_hint(self):
        mb = self._batcher()
        drv = mb._client.driver
        with mb._rate_lock:
            mb._load_rps = 5000.0
        mb._adapt()
        mb.stop()
        assert drv.loads[-1] is None

    def test_dispatch_span_carries_adaptation_state(self, monkeypatch):
        """/debug/traces must show WHY a request waited: the batch span
        carries the target, deadline, and the load that set them."""
        from gatekeeper_tpu.obs import trace as obstrace
        from gatekeeper_tpu.webhook import server as websrv

        seen = {}
        real = obstrace.batch_span

        def capture(name, spans, **attrs):
            seen.update(attrs)
            return real(name, spans, **attrs)

        monkeypatch.setattr(websrv.obstrace, "batch_span", capture)

        class _SlowClient(_ModelClient):
            def review(self, obj, tracing=False):
                time.sleep(0.01)  # idle fast path: slow enough to queue
                return None

            def review_batch(self, objs, tracing=False):
                time.sleep(0.01)
                return [None] * len(objs)

        mb = MicroBatcher(_SlowClient(), window_s=0.05)
        try:
            done = threading.Barrier(5)

            def call():
                with obstrace.root_span("test.request"):
                    mb.review(object())
                done.wait(timeout=10)

            threads = [threading.Thread(target=call) for _ in range(4)]
            for t in threads:
                t.start()
            done.wait(timeout=10)
            for t in threads:
                t.join()
            assert "batch_target" in seen
            assert "batch_deadline_ms" in seen
            assert "offered_load_rps" in seen
            assert "batch_size" in seen
        finally:
            mb.stop()

    def test_batcher_state_exported_with_replica_id(self):
        from gatekeeper_tpu.metrics.catalog import record_batcher_state
        from gatekeeper_tpu.metrics.views import global_registry

        set_replica_id("r-test-7")
        record_batcher_state(17, 4.5, 1234.0)
        rows = global_registry().view_rows("webhook_batch_target_size")
        assert rows.get(("r-test-7",)) == 17.0
        rows = global_registry().view_rows("webhook_offered_load_rps")
        assert rows.get(("r-test-7",)) == 1234.0
        rows = global_registry().view_rows("webhook_batch_deadline_ms")
        assert rows.get(("r-test-7",)) == 4.5


# ---- aux server idempotent starts ------------------------------------------


class TestAuxServerIdempotentStart:
    def _double_start(self, server, probe_path):
        server.start()
        first_port = server.port
        try:
            server.port = 0
            server.start()  # replaces, never leaks
            assert server.port != 0
            with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}{probe_path}", timeout=10
            ) as resp:
                assert resp.status == 200
            # the first port was released by the replacement
            import socket

            s = socket.socket()
            try:
                s.bind(("127.0.0.1", first_port))
            finally:
                s.close()
        finally:
            server.stop()

    def test_health_server_start_is_idempotent(self):
        from gatekeeper_tpu.main import HealthServer

        self._double_start(
            HealthServer(port=0, readiness_check=lambda: True), "/healthz"
        )

    def test_profile_server_start_is_idempotent(self):
        from gatekeeper_tpu.main import ProfileServer

        self._double_start(ProfileServer(port=0), "/debug/pprof/")


# ---- replica identity stamping ---------------------------------------------


class TestReplicaIdentity:
    def test_replica_id_on_root_spans(self):
        from gatekeeper_tpu.obs import trace as obstrace

        set_replica_id("r9")
        with obstrace.root_span("unit.test") as sp:
            pass
        assert sp.attrs.get("replica_id") == "r9"

    def test_no_replica_id_means_no_attr(self):
        from gatekeeper_tpu.obs import trace as obstrace

        set_replica_id("")
        with obstrace.root_span("unit.test") as sp:
            pass
        assert "replica_id" not in sp.attrs

    def test_replica_id_in_slo_payload(self):
        from gatekeeper_tpu.obs.slo import SLOEngine

        set_replica_id("r42")
        out = SLOEngine().evaluate()
        assert out["replica_id"] == "r42"
        set_replica_id("")
        out = SLOEngine().evaluate()
        assert "replica_id" not in out

    def test_replica_up_labelled(self):
        from gatekeeper_tpu.metrics.catalog import record_replica_up
        from gatekeeper_tpu.metrics.views import global_registry

        set_replica_id("r-up")
        record_replica_up()
        rows = global_registry().view_rows("replica_up")
        assert rows.get(("r-up",)) == 1.0

    def test_replica_id_env_fallback(self, monkeypatch):
        from gatekeeper_tpu import util as gkutil

        monkeypatch.setattr(gkutil, "_replica_id", None)
        monkeypatch.setenv("GK_REPLICA_ID", "env-r1")
        assert replica_id() == "env-r1"
