"""Chaos suite: webhook + audit + watch driven under seeded fault
schedules (gatekeeper_tpu/faults/), asserting the degradation ladder of
docs/failure-modes.md:

  - the TPU circuit breaker trips after N injected dispatch failures,
    serves interpreter-identical verdicts while open, and returns to the
    device after recovery probes succeed
  - no admission request exceeds its deadline budget by more than one
    batch window under injected hangs — exhaustion is an explicit
    fail-open/closed decision, never a socket timeout
  - the audit loop survives a full kube outage (every HTTP send fails)
    and resumes, with the failure streak visible in metrics
  - the watch pump survives injected delivery faults

Everything is deterministic: fixed seeds, probability-1/count-limited
schedules, and bounded waits (hangs are plane-released).  The suite runs
inside the tier-1 `-m 'not slow'` selection; the conftest leak fixture
fails any test that leaves the plane enabled.
"""

import json
import queue
import threading
import time

import pytest

from gatekeeper_tpu import deadline, faults
from gatekeeper_tpu.audit import AuditManager
from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.deadline import DeadlineExceeded
from gatekeeper_tpu.faults import FaultError, FaultPlane, FaultRule
from gatekeeper_tpu.kube.apiserver import KubeApiServer
from gatekeeper_tpu.kube.http_client import HttpKube
from gatekeeper_tpu.kube.inmem import InMemoryKube
from gatekeeper_tpu.metrics import Reporters
from gatekeeper_tpu.metrics.views import Registry
from gatekeeper_tpu.ops.breaker import CLOSED, OPEN
from gatekeeper_tpu.ops.driver import TpuDriver
from gatekeeper_tpu.target.target import AugmentedReview
from gatekeeper_tpu.watch.manager import WatchManager
from gatekeeper_tpu.webhook import BatcherStopped, MicroBatcher

from .test_controllers import CONSTRAINT, TEMPLATE

pytestmark = pytest.mark.chaos

SEED = 1234
PROBE_NAME = "gk-breaker-probe"


@pytest.fixture()
def fault_plane():
    plane = faults.install(seed=SEED)
    yield plane
    faults.uninstall()


def ns_review(name, labels=None):
    return {
        "uid": f"uid-{name}",
        "kind": {"group": "", "version": "v1", "kind": "Namespace"},
        "name": name,
        "namespace": "",
        "operation": "CREATE",
        "userInfo": {"username": "alice"},
        "object": {
            "apiVersion": "v1",
            "kind": "Namespace",
            "metadata": {"name": name, "labels": labels or {}},
        },
    }


def review_sig(responses):
    return sorted((r.msg, r.enforcement_action) for r in responses.results())


def tpu_client(threshold=3, cooldown=0.05):
    driver = TpuDriver(
        breaker_threshold=threshold, breaker_cooldown_s=cooldown
    )
    driver.DEVICE_MIN_CELLS = 0  # force the device path for unique content
    client = Client(driver=driver)
    client.add_template(TEMPLATE)
    client.add_constraint(CONSTRAINT)
    return client, driver


def interp_client():
    client = Client()
    client.add_template(TEMPLATE)
    client.add_constraint(CONSTRAINT)
    return client


def wait_until(cond, timeout_s=5.0, step_s=0.01):
    end = time.monotonic() + timeout_s
    while time.monotonic() < end:
        if cond():
            return True
        time.sleep(step_s)
    return cond()


class TestCircuitBreaker:
    def test_trips_serves_interp_identical_and_recovers(self, fault_plane):
        client, driver = tpu_client(threshold=3, cooldown=0.05)
        oracle = interp_client()

        dispatched = []  # review names per compute_masks call
        orig = driver.compute_masks

        def counting(reviews):
            dispatched.extend(r.get("name", "?") for r in reviews)
            return orig(reviews)

        driver.compute_masks = counting

        def traffic_dispatches():
            return [n for n in dispatched if n != PROBE_NAME]

        # healthy: device path serves and the breaker stays closed
        req = ns_review("warm")
        got = client.review(AugmentedReview(admission_request=req))
        want = oracle.review(AugmentedReview(admission_request=req))
        assert review_sig(got) == review_sig(want)
        assert driver.breaker.state == CLOSED
        assert traffic_dispatches(), "healthy review must hit the device"

        fault_plane.add(faults.TPU_DISPATCH, FaultRule(mode="error"))

        # N consecutive injected dispatch failures trip the breaker; each
        # failed batch STILL answers correctly (interpreter fallback)
        for i in range(3):
            req = ns_review(f"fail-{i}")
            got = client.review(AugmentedReview(admission_request=req))
            want = oracle.review(AugmentedReview(admission_request=req))
            assert review_sig(got) == review_sig(want)
        st = driver.breaker.status()
        assert st["state"] != "closed"
        assert st["trips"] >= 1

        # while degraded: traffic never reaches the device (background
        # probes may; they carry the probe review name) and every verdict
        # is interpreter-identical — deny and allow cases both
        n_before = len(traffic_dispatches())
        for i in range(4):
            labels = {"gatekeeper": "on"} if i % 2 else None
            req = ns_review(f"degraded-{i}", labels=labels)
            got = client.review(AugmentedReview(admission_request=req))
            want = oracle.review(AugmentedReview(admission_request=req))
            assert review_sig(got) == review_sig(want)
            if labels:
                assert review_sig(got) == []
            else:
                assert len(review_sig(got)) == 1
        assert len(traffic_dispatches()) == n_before, (
            "open breaker must keep admission traffic off the device"
        )

        # recovery: clear the schedule; the background half-open probe
        # closes the breaker without any real traffic
        fault_plane.clear(faults.TPU_DISPATCH)
        assert wait_until(lambda: driver.breaker.state == CLOSED), (
            f"breaker did not recover: {driver.breaker.status()}"
        )
        assert dispatched.count(PROBE_NAME) >= 1, "recovery must be probe-driven"

        # traffic returns to the TPU
        req = ns_review("recovered")
        got = client.review(AugmentedReview(admission_request=req))
        assert review_sig(got) == review_sig(
            oracle.review(AugmentedReview(admission_request=req))
        )
        assert len(traffic_dispatches()) > n_before, (
            "closed breaker must route traffic back to the device"
        )
        assert driver.breaker_status()["consecutive_failures"] == 0

    def test_breaker_transitions_land_in_metrics(self, fault_plane):
        from gatekeeper_tpu.metrics.views import global_registry

        client, driver = tpu_client(threshold=2, cooldown=30.0)
        fault_plane.add(faults.TPU_DISPATCH, FaultRule(mode="error"))
        for i in range(2):
            client.review(
                AugmentedReview(admission_request=ns_review(f"m-{i}"))
            )
        assert driver.breaker.state == OPEN
        rows = global_registry().view_rows("tpu_breaker_state")
        assert rows.get(()) == 2.0  # open
        trips = global_registry().view_rows("tpu_breaker_trips")
        assert trips.get(()) >= 1.0
        fault_plane.clear(faults.TPU_DISPATCH)
        driver.breaker.probe_now()
        assert driver.breaker.state == CLOSED
        rows = global_registry().view_rows("tpu_breaker_state")
        assert rows.get(()) == 0.0  # closed again

    def test_degraded_seconds_span_failed_trials(self):
        """A failed half-open trial restarts the cooldown clock but must
        NOT zero the degraded-time metric: degraded_seconds spans the
        whole outage, not just the last cooldown interval."""
        from gatekeeper_tpu.ops.breaker import CircuitBreaker

        t = [0.0]
        cb = CircuitBreaker(
            failure_threshold=1, cooldown_s=5.0, clock=lambda: t[0]
        )
        cb.record_failure()  # trips at t=0
        t[0] = 10.0
        assert cb.allow()  # lazy half-open trial
        cb.record_failure()  # failed trial: re-open
        t[0] = 20.0
        assert cb.status()["degraded_seconds"] == 20.0
        assert cb.allow()
        cb.record_success()
        assert cb.state == CLOSED
        assert cb.status()["degraded_seconds"] == 20.0  # frozen on close

    def test_breaker_state_visible_on_health_endpoints(self):
        import urllib.request

        from gatekeeper_tpu.webhook import ValidationHandler, WebhookServer

        client, driver = tpu_client()
        handler = ValidationHandler(client, kube=InMemoryKube())
        srv = WebhookServer(
            handler, port=0,
            health_status=lambda: {"tpu_breaker": driver.breaker_status()},
        )
        srv.start()
        try:
            def get(path):
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}{path}", timeout=5
                ) as r:
                    return r.status, r.read()

            code, body = get("/healthz")
            assert (code, body) == (200, b"ok")
            driver.breaker.trip()
            code, body = get("/healthz")
            # degraded-but-serving: still 200 (no restart), marker visible
            assert (code, body) == (200, b"ok (degraded)")
            code, body = get("/statusz")
            st = json.loads(body)["tpu_breaker"]
            assert code == 200
            assert st["state"] == "open" and st["trips"] == 1
            driver.breaker.record_success()
            code, body = get("/healthz")
            assert (code, body) == (200, b"ok")
        finally:
            srv.stop()

    def test_degraded_audit_matches_interpreter(self):
        client, driver = tpu_client()
        oracle = interp_client()
        for c in (client, oracle):
            for i in range(3):
                c.add_data({"apiVersion": "v1", "kind": "Namespace",
                            "metadata": {"name": f"bad-{i}", "labels": {}}})
            c.add_data({"apiVersion": "v1", "kind": "Namespace",
                        "metadata": {"name": "good",
                                     "labels": {"gatekeeper": "on"}}})
        want_resp, want_totals = oracle.audit_capped(20)

        driver.breaker.trip()

        def no_device(*a, **k):
            raise AssertionError("device sweep ran while the breaker is open")

        driver._audit_sweep = no_device
        got_resp, got_totals = client.audit_capped(20)
        assert got_totals == want_totals
        assert sorted(r.msg for r in got_resp.results()) == sorted(
            r.msg for r in want_resp.results()
        )
        driver.breaker.record_success()  # close it again


class TestDeadlineBudget:
    def test_no_request_overshoots_budget_under_injected_hangs(
        self, fault_plane
    ):
        client, driver = tpu_client()
        window = 0.01
        budget = 0.15
        mb = MicroBatcher(client, window_s=window)
        fault_plane.add(
            faults.TPU_DISPATCH,
            FaultRule(mode="hang", hang_s=2.0),
        )
        try:
            for i in range(3):
                with deadline.budget(budget):
                    t0 = time.monotonic()
                    with pytest.raises(DeadlineExceeded):
                        mb.review(AugmentedReview(
                            admission_request=ns_review(f"hang-{i}")
                        ))
                    dur = time.monotonic() - t0
                # acceptance bound: budget + one batch window (plus
                # scheduler slack far below the 2s injected hang)
                assert dur <= budget + window + 0.1, (
                    f"request {i} took {dur:.3f}s against a "
                    f"{budget:.3f}s budget"
                )
        finally:
            fault_plane.release_hangs()
            mb.stop()

    def test_expired_budget_refused_before_enqueue(self):
        client, driver = tpu_client()
        mb = MicroBatcher(client, window_s=0.01)
        try:
            token = deadline.push(-1.0)  # already expired
            try:
                with pytest.raises(DeadlineExceeded):
                    mb.review(AugmentedReview(
                        admission_request=ns_review("expired")
                    ))
            finally:
                deadline.pop(token)
        finally:
            mb.stop()

    def test_server_answers_within_budget_not_socket_timeout(
        self, fault_plane
    ):
        """End-to-end: a hung dispatch yields a well-formed 504 deny
        AdmissionReview inside budget + window, not a hung socket."""
        import urllib.request

        from gatekeeper_tpu.webhook import ValidationHandler, WebhookServer

        client, driver = tpu_client()
        mb = MicroBatcher(client, window_s=0.01)
        handler = ValidationHandler(mb, kube=InMemoryKube())
        srv = WebhookServer(handler, port=0, deadline_budget_s=0.15)
        srv.start()
        fault_plane.add(
            faults.TPU_DISPATCH, FaultRule(mode="hang", hang_s=2.0)
        )
        try:
            body = json.dumps({
                "apiVersion": "admission.k8s.io/v1beta1",
                "kind": "AdmissionReview",
                "request": ns_review("e2e-hang"),
            }).encode()
            r = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/v1/admit", data=body,
                headers={"Content-Type": "application/json"},
            )
            t0 = time.monotonic()
            with urllib.request.urlopen(r, timeout=5) as resp:
                out = json.loads(resp.read())
            dur = time.monotonic() - t0
            assert dur < 1.0, f"response took {dur:.3f}s (hang leaked)"
            assert out["response"]["allowed"] is False
            assert out["response"]["status"]["code"] == 504
            assert out["response"]["status"]["message"] == (
                "admission deadline budget exhausted"
            )
        finally:
            fault_plane.release_hangs()
            srv.stop()
            mb.stop()


class TestAuditOutage:
    def test_audit_survives_full_kube_outage_and_resumes(self, fault_plane):
        srv = KubeApiServer()
        srv.start()
        try:
            kube = HttpKube(srv.url, discovery_retry_s=1.0)
            client = interp_client()
            # register the synthesized constraint CRD so the status-write
            # path can list (finding no constraint objects is fine)
            kube.create(client.add_template(TEMPLATE))
            for i in range(2):
                kube.create({"apiVersion": "v1", "kind": "Namespace",
                             "metadata": {"name": f"bad-{i}", "labels": {}}})
            reporter = Reporters(Registry())
            am = AuditManager(kube, client, reporter=reporter,
                              interval_s=3600.0)

            assert am.run_once_guarded() is True
            assert am.last_run_status == "ok"
            assert am.consecutive_failures == 0

            # full outage: every kube HTTP send fails
            fault_plane.add(faults.KUBE_SEND, FaultRule(mode="error"))
            assert am.run_once_guarded() is False
            assert am.run_once_guarded() is False
            assert am.consecutive_failures == 2
            assert am.last_run_status == "error"
            rows = reporter.registry.view_rows("audit_consecutive_failures")
            assert rows.get(()) == 2.0
            assert reporter.registry.view_rows(
                "audit_last_run_status"
            ).get(()) == 0.0

            # recovery: the very next sweep succeeds and finds violations
            fault_plane.clear(faults.KUBE_SEND)
            assert am.run_once_guarded() is True
            assert am.consecutive_failures == 0
            assert am.last_run_status == "ok"
            assert reporter.registry.view_rows(
                "audit_last_run_status"
            ).get(()) == 1.0
            update_lists = am.audit_once()
            assert update_lists, "post-outage sweep must find violations"
            (violations,) = update_lists.values()
            assert {v.name for v in violations} == {"bad-0", "bad-1"}
        finally:
            srv.stop()


class TestWatchFaults:
    def test_pump_survives_injected_delivery_drops(self, fault_plane):
        kube = InMemoryKube()
        wm = WatchManager(kube)
        reg = wm.new_registrar("chaos")
        ns_gvk = ("", "v1", "Namespace")
        reg.add_watch(ns_gvk)
        assert wait_until(lambda: wm.replays_active() == 0)
        try:
            # exactly the first two deliveries drop; the pump survives
            fault_plane.add(
                faults.WATCH_DELIVER, FaultRule(mode="error", count=2)
            )
            for i in range(5):
                kube.create({"apiVersion": "v1", "kind": "Namespace",
                             "metadata": {"name": f"ns-{i}"}})
            got = []
            end = time.monotonic() + 5.0
            while len(got) < 3 and time.monotonic() < end:
                try:
                    got.append(reg.events.get(timeout=0.2))
                except queue.Empty:
                    pass
            names = [ev.object["metadata"]["name"] for _gvk, ev in got]
            assert names == ["ns-2", "ns-3", "ns-4"]
            # schedule spent: later events flow normally
            kube.create({"apiVersion": "v1", "kind": "Namespace",
                         "metadata": {"name": "ns-after"}})
            _gvk, ev = reg.events.get(timeout=2.0)
            assert ev.object["metadata"]["name"] == "ns-after"
        finally:
            wm.stop()


class TestBatcherShutdown:
    def test_stop_drains_pending_and_rejects_new_enqueues(self):
        client = interp_client()
        entered = threading.Event()
        gate = threading.Event()
        orig_batch = client.review_batch

        def blocking_batch(objs, tracing=False):
            entered.set()
            gate.wait(5.0)
            return orig_batch(objs, tracing=tracing)

        client.review_batch = blocking_batch
        mb = MicroBatcher(client, window_s=0.01)
        results = {}

        def call(key, name):
            try:
                results[key] = mb.review(
                    AugmentedReview(admission_request=ns_review(name))
                )
            except Exception as e:
                results[key] = e

        # occupy the batch loop with a genuinely in-flight batch
        mb._busy = True  # steer the first request into the queue
        t1 = threading.Thread(target=call, args=("t1", "first"))
        t1.start()
        assert entered.wait(5.0), "batch loop never picked up the request"
        # now enqueue a second request behind the in-flight batch
        t2 = threading.Thread(target=call, args=("t2", "second"))
        t2.start()
        assert wait_until(lambda: len(mb._pending) == 1)

        # stop() while a request is pending: it must get a shutdown error
        # (the old code left it waiting on its event forever)
        stopper = threading.Thread(target=mb.stop)
        stopper.start()
        t2.join(timeout=5.0)
        assert not t2.is_alive()
        assert isinstance(results["t2"], BatcherStopped)

        # enqueues after stop() fail fast
        with pytest.raises(BatcherStopped):
            mb.review(AugmentedReview(admission_request=ns_review("third")))

        # release the in-flight batch: its caller still gets its answer
        gate.set()
        t1.join(timeout=5.0)
        stopper.join(timeout=5.0)
        assert not t1.is_alive() and not stopper.is_alive()
        assert not isinstance(results["t1"], Exception)
        assert len(results["t1"].results()) == 1


class TestReconnectBackoff:
    """Bounds of the watch reconnect schedule (syncutil.Backoff, used by
    HttpWatcher._pump): capped exponential with downward jitter — the cap
    is HARD (no interval ever exceeds it, jittered or not) and the jitter
    desynchronizes a fleet of reconnecting watchers without shrinking any
    interval below half its nominal value.  (Lives here rather than
    test_http_kube.py because that module needs `cryptography` to
    collect.)"""

    def test_schedule_bounds_and_hard_cap(self):
        import random as _random

        from gatekeeper_tpu.syncutil import Backoff

        b = Backoff(base=0.05, factor=2.0, cap=2.0, jitter=0.5,
                    rng=_random.Random(7))
        nominal = 0.05
        for _ in range(16):
            v = b.next()
            hi = min(nominal, 2.0)
            assert hi * 0.5 - 1e-9 <= v <= hi + 1e-9
            assert v <= 2.0  # hard cap survives jitter
            nominal = min(nominal * 2.0, 2.0)

    def test_no_jitter_is_the_exact_ladder(self):
        from gatekeeper_tpu.syncutil import Backoff

        b = Backoff(base=0.05, factor=2.0, cap=2.0, jitter=0.0)
        got = [round(b.next(), 4) for _ in range(8)]
        assert got == [0.05, 0.1, 0.2, 0.4, 0.8, 1.6, 2.0, 2.0]
        b.reset()
        assert b.next() == 0.05

    def test_seeded_schedules_deterministic_and_desynchronized(self):
        import random as _random

        from gatekeeper_tpu.syncutil import Backoff

        def schedule(seed):
            b = Backoff(rng=_random.Random(seed))
            return [b.next() for _ in range(10)]

        assert schedule(1) == schedule(1)
        assert schedule(1) != schedule(2)  # the anti-storm property

    def test_watcher_pump_uses_jittered_capped_schedule(self):
        from gatekeeper_tpu.kube.http_client import HttpWatcher

        assert HttpWatcher.RECONNECT_BASE_S == 0.05
        assert HttpWatcher.RECONNECT_CAP_S == 2.0
        assert 0.0 < HttpWatcher.RECONNECT_JITTER < 1.0


class TestFaultPlane:
    def test_inert_by_default(self):
        assert faults.ENABLED is False
        faults.fire(faults.TPU_DISPATCH)  # no plane installed: a no-op
        # call sites gated on the flag inject nothing anywhere
        client, driver = tpu_client()
        got = client.review(
            AugmentedReview(admission_request=ns_review("inert"))
        )
        assert len(got.results()) == 1
        assert driver.breaker.state == CLOSED

    def test_seeded_schedules_are_deterministic(self):
        def decisions(seed):
            plane = FaultPlane(seed=seed)
            plane.add("pt", FaultRule(mode="error", probability=0.5))
            out = []
            for _ in range(64):
                try:
                    plane.fire("pt")
                    out.append(0)
                except FaultError:
                    out.append(1)
            return out

        a, b, c = decisions(5), decisions(5), decisions(6)
        assert a == b
        assert a != c
        assert 10 < sum(a) < 54  # probability actually applied

    def test_count_after_and_latency_semantics(self):
        plane = FaultPlane(seed=0)
        rule = plane.add("pt", FaultRule(mode="error", count=2, after=1))
        outcomes = []
        for _ in range(5):
            try:
                plane.fire("pt")
                outcomes.append("ok")
            except FaultError:
                outcomes.append("err")
        assert outcomes == ["ok", "err", "err", "ok", "ok"]
        assert rule.fires == 2
        lat = plane.add("lat", FaultRule(mode="latency", latency_s=0.05))
        t0 = time.monotonic()
        plane.fire("lat")
        assert time.monotonic() - t0 >= 0.04

    def test_hang_is_bounded_and_releasable(self):
        plane = FaultPlane(seed=0)
        plane.add("h", FaultRule(mode="hang", hang_s=10.0))
        done = threading.Event()

        def hang_call():
            plane.fire("h")
            done.set()

        t = threading.Thread(target=hang_call, daemon=True)
        t.start()
        assert not done.wait(0.1), "hang returned immediately"
        plane.release_hangs()
        assert done.wait(2.0), "release did not unblock the hang"


# ---- snapshot / warm-resume chaos (ISSUE 3 satellite) -----------------------
# Every corruption or injected fault must degrade to the COLD path — no
# crash, no partial state — with snapshot_restore_outcome_total{outcome=
# "fallback"} incremented and the next audit sweep still correct.

import os

from gatekeeper_tpu.metrics.views import global_registry
from gatekeeper_tpu.snapshot import SnapshotLoader, Snapshotter
from gatekeeper_tpu.snapshot import format as snapfmt

from .test_snapshot import (
    TEMPLATE as SNAP_TEMPLATE,
    CONSTRAINT as SNAP_CONSTRAINT,
    audit_sig,
    build_cluster,
    fresh_client,
    make_client,
    outcome_counts,
)


class TestSnapshotChaos:
    def _written(self, snap_dir, n=6):
        kube = build_cluster(n=n)
        client = make_client(kube)
        sig, _ = audit_sig(client)
        snapper = Snapshotter(
            client, str(snap_dir), capture_delta=False
        )
        assert snapper.write_once() is not None
        return kube, sig

    def _restore_expect_fallback(self, snap_dir, kube, cold_sig):
        before = outcome_counts().get("fallback", 0)
        client = fresh_client()
        outcome = SnapshotLoader(str(snap_dir)).restore(client, kube)
        assert outcome == "fallback"
        assert outcome_counts().get("fallback", 0) == before + 1
        # the cold path still produces the oracle's verdicts
        client.add_template(SNAP_TEMPLATE)
        client.add_constraint(SNAP_CONSTRAINT)
        for obj in kube.list(("", "v1", "Namespace")):
            client.add_data(obj)
        sig, _ = audit_sig(client)
        assert sig == cold_sig

    def _corrupt(self, snap_dir, fname, mutate):
        snap = os.path.join(
            str(snap_dir), snapfmt.list_snapshots(str(snap_dir))[0]
        )
        path = os.path.join(snap, fname)
        mutate(path)

    def test_corrupt_manifest_falls_back_clean(self, tmp_path):
        kube, sig = self._written(tmp_path)

        def mutate(path):
            blob = open(path).read()
            open(path, "w").write(blob.replace('"schema": 1', '"schema": 9'))

        self._corrupt(tmp_path, snapfmt.MANIFEST, mutate)
        self._restore_expect_fallback(tmp_path, kube, sig)

    def test_truncated_array_falls_back_clean(self, tmp_path):
        kube, sig = self._written(tmp_path)

        def mutate(path):
            blob = open(path, "rb").read()
            open(path, "wb").write(blob[: max(1, len(blob) // 3)])

        self._corrupt(tmp_path, snapfmt.ARRAYS, mutate)
        self._restore_expect_fallback(tmp_path, kube, sig)

    def test_wrong_hmac_falls_back_clean(self, tmp_path):
        kube, sig = self._written(tmp_path)

        def mutate(path):
            manifest = json.load(open(path))
            manifest["hmac"] = "f" * 64
            json.dump(manifest, open(path, "w"))

        self._corrupt(tmp_path, snapfmt.MANIFEST, mutate)
        self._restore_expect_fallback(tmp_path, kube, sig)

    def test_stale_resource_versions_fall_back_clean(self, tmp_path):
        kube, _sig = self._written(tmp_path)
        gvk = ("", "v1", "Namespace")
        for obj in kube.list(gvk):  # every RV moves while "down"
            obj["metadata"]["labels"]["churn"] = "y"
            kube.update(obj)
        before = outcome_counts().get("fallback", 0)
        client = fresh_client()
        outcome = SnapshotLoader(str(tmp_path)).restore(client, kube)
        assert outcome == "fallback"
        assert outcome_counts().get("fallback", 0) == before + 1
        warm_sig, _ = audit_sig(client)  # safe: everything re-packs
        oracle = make_client(kube)
        cold_sig, _ = audit_sig(oracle)
        assert warm_sig == cold_sig

    def test_injected_load_fault_falls_back(self, tmp_path, fault_plane):
        kube, sig = self._written(tmp_path)
        fault_plane.add(faults.SNAPSHOT_LOAD, FaultRule(mode="error"))
        self._restore_expect_fallback(tmp_path, kube, sig)

    def test_injected_resync_fault_wipes_to_cold(self, tmp_path, fault_plane):
        kube, sig = self._written(tmp_path)
        fault_plane.add(faults.SNAPSHOT_RESYNC, FaultRule(mode="error"))
        before = outcome_counts().get("fallback", 0)
        client = fresh_client()
        outcome = SnapshotLoader(str(tmp_path)).restore(client, kube)
        assert outcome == "fallback"
        assert outcome_counts().get("fallback", 0) == before + 1
        # mid-restore failure wiped the partial state: the store is empty
        # and the cold path rebuilds to the oracle verdicts
        assert client.driver._audit_pack.rp is None
        client.add_template(SNAP_TEMPLATE)
        client.add_constraint(SNAP_CONSTRAINT)
        for obj in kube.list(("", "v1", "Namespace")):
            client.add_data(obj)
        cold_sig, _ = audit_sig(client)
        assert cold_sig == sig

    def test_injected_write_fault_leaves_no_partial_snapshot(
        self, tmp_path, fault_plane
    ):
        kube = build_cluster(n=4)
        client = make_client(kube)
        audit_sig(client)
        fault_plane.add(
            faults.SNAPSHOT_WRITE, FaultRule(mode="error", count=1)
        )
        snapper = Snapshotter(client, str(tmp_path), capture_delta=False)
        assert snapper.write_once() is None
        assert snapper.last_error
        # no partial or temp dirs survive a failed write
        leftovers = [
            n for n in os.listdir(str(tmp_path))
            if n.startswith(snapfmt.TMP_PREFIX)
        ]
        assert leftovers == []
        assert snapfmt.list_snapshots(str(tmp_path)) == []
        # the audit loop is unaffected by persistence failures
        mgr = AuditManager(
            kube, client, from_cache=True, snapshotter=snapper,
        )
        assert mgr.run_once_guarded() is True
        # and the retry (fault exhausted) succeeds
        snapper._last_write = 0.0
        assert snapper.write_once() is not None


class TestGracefulDrain:
    """ISSUE 8: the drain protocol's two halves — the micro-batcher
    flush bounded by its deadline budget, and the server-side intake
    stop (docs/fleet.md)."""

    def test_drain_under_deadline_budget_never_exceeds_it(
        self, fault_plane
    ):
        """ISSUE 8 satellite: a graceful drain with a 10ms deadline
        budget returns within it (plus scheduler slack) even when the
        in-flight batch is wedged on a 2s injected hang — the drain
        reports `overran`, it never waits the hang out."""
        client, driver = tpu_client()
        mb = MicroBatcher(client, window_s=0.01)
        fault_plane.add(
            faults.TPU_DISPATCH, FaultRule(mode="hang", hang_s=2.0)
        )
        result = {}

        def call():
            # a deadline-carrying request always takes the QUEUED path
            # (the inline fast path is uninterruptible), so the batch
            # loop — not this thread — owns the wedged dispatch.  The
            # budget is generous: only the wedge bounds this test.
            token = deadline.push(30.0)
            try:
                result["r"] = mb.review(AugmentedReview(
                    admission_request=ns_review("drain-hang")
                ))
            except Exception as e:
                result["r"] = e
            finally:
                deadline.pop(token)

        try:
            t = threading.Thread(target=call)
            t.start()
            # the batch loop picks it up and wedges inside the dispatch
            # (observing the 1-element queue in between would race the
            # loop's sub-ms grab — this state is the stable one)
            assert wait_until(
                lambda: mb._busy and not mb._pending, timeout_s=5.0
            ), "batch loop never picked up the wedged request"
            t0 = time.monotonic()
            stats = mb.drain(0.010)
            dur = time.monotonic() - t0
            assert dur <= 0.010 + 0.1, (
                f"drain took {dur:.3f}s against a 10ms budget"
            )
            assert stats["overran"] is True
            assert stats["drained"] is False
        finally:
            fault_plane.release_hangs()
            t.join(timeout=5.0)
            mb.stop()

    def test_drain_of_idle_batcher_returns_immediately(self):
        client, driver = tpu_client()
        mb = MicroBatcher(client, window_s=0.01)
        try:
            stats = mb.drain(0.010)
            assert stats == {
                "pending_start": 0, "drained": True, "overran": False,
                "drain_ms": stats["drain_ms"],
            }
            assert stats["drain_ms"] <= 10.0
        finally:
            mb.stop()

    def test_draining_server_refuses_new_admissions_explicitly(self):
        """The drain protocol's intake side: a draining server answers
        503 (the front door fails over), /readyz goes not-ready, and
        /healthz stays 200 — then drain(False) restores service."""
        import urllib.error
        import urllib.request

        from gatekeeper_tpu.webhook import ValidationHandler, WebhookServer

        client = interp_client()
        handler = ValidationHandler(client, kube=InMemoryKube())
        srv = WebhookServer(handler, port=0)
        srv.start()
        try:
            body = json.dumps({"request": ns_review("pre-drain")}).encode()

            def post():
                r = urllib.request.Request(
                    f"http://127.0.0.1:{srv.port}/v1/admit", data=body,
                    headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(r, timeout=5) as resp:
                    return resp.status, json.loads(resp.read())

            def get(path):
                try:
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{srv.port}{path}", timeout=5
                    ) as r:
                        return r.status, r.read()
                except urllib.error.HTTPError as e:
                    return e.code, e.read()

            assert post()[0] == 200
            srv.drain()
            code, _ = get("/readyz")
            assert code == 503
            assert get("/healthz")[0] == 200
            with pytest.raises(urllib.error.HTTPError) as ei:
                post()
            assert ei.value.code == 503
            assert b"draining" in ei.value.read()
            srv.drain(False)
            assert post()[0] == 200
            assert get("/readyz")[0] == 200
        finally:
            srv.stop()


class TestMeshDispatchStall:
    """ISSUE 8: a wedged mesh collective must not hold the sweep (or the
    dispatch gate) forever — the watchdog abandons it, trips the breaker
    (interpreter-identical verdicts meanwhile), and re-shards the sweep
    one step narrower; the rebasing full sweep at the new width stays
    byte-parity with the oracle."""

    def _populate(self, *clients, n=6):
        for c in clients:
            for i in range(n):
                labels = {"gatekeeper": "on"} if i % 2 else {}
                c.add_data({"apiVersion": "v1", "kind": "Namespace",
                            "metadata": {"name": f"m-{i}",
                                         "labels": labels}})

    def _audit_sig(self, client):
        resp, totals = client.audit_capped(20)
        return sorted(r.msg for r in resp.results()), totals

    def test_stall_trips_breaker_and_narrows_mesh(self, fault_plane):
        from gatekeeper_tpu.parallel.mesh import DISPATCH_LOCK

        driver = TpuDriver(
            breaker_threshold=3, breaker_cooldown_s=30.0,
            mesh_watchdog_s=0.25,
        )
        driver.DEVICE_MIN_CELLS = 0
        client = Client(driver=driver)
        client.add_template(TEMPLATE)
        client.add_constraint(CONSTRAINT)
        oracle = interp_client()
        self._populate(client, oracle)
        driver.set_mesh(True, width=4)
        want = self._audit_sig(oracle)

        revocations_before = DISPATCH_LOCK.revocations
        # the collective wedges (bounded, releasable) INSIDE the gate —
        # exactly what a stuck AllReduce rendezvous looks like
        fault_plane.add(
            faults.MESH_DISPATCH_STALL,
            FaultRule(mode="hang", hang_s=10.0, count=1),
        )
        got = self._audit_sig(client)
        assert got == want, "stalled sweep must still answer (interp tier)"
        assert driver.breaker.state == OPEN, driver.breaker.status()
        assert driver.mesh_layout() == 2, (
            "stall must re-shard the sweep one step narrower"
        )
        assert DISPATCH_LOCK.revocations == revocations_before + 1
        from gatekeeper_tpu.metrics.views import global_registry

        assert global_registry().view_rows(
            "mesh_dispatch_stalls_total"
        ).get(()) >= 1.0
        assert global_registry().view_rows(
            "mesh_sweep_width"
        ).get(()) == 2.0

        # while degraded every sweep is interpreter-identical
        assert self._audit_sig(client) == want

        # unwedge the abandoned dispatch and let it finish ALONE before
        # any new device work (enqueue-order discipline)
        fault_plane.release_hangs()
        time.sleep(0.3)
        fault_plane.clear(faults.MESH_DISPATCH_STALL)
        # the first width-2 dispatch pays the SPMD trace+compile INSIDE
        # the guarded region (this jax cannot pre-populate the jit cache
        # from lower().compile()), so the recovery phase needs a budget
        # that covers a cold compile — exactly why the production
        # default is 30s, not sub-second
        driver.mesh_watchdog_s = 60.0
        assert driver.breaker.probe_now(), driver.breaker.status()
        # the next device sweep runs at the narrower width and rebases
        # via one full dispatch — parity preserved
        assert self._audit_sig(client) == want
        stats = driver.last_sweep_stats
        assert stats.get("shards") == 2.0, stats
        assert not stats.get("cached")

    def test_second_stall_degrades_to_single_device(self, fault_plane):
        driver = TpuDriver(
            breaker_threshold=3, breaker_cooldown_s=30.0,
            mesh_watchdog_s=0.25,
        )
        driver.DEVICE_MIN_CELLS = 0
        client = Client(driver=driver)
        client.add_template(TEMPLATE)
        client.add_constraint(CONSTRAINT)
        oracle = interp_client()
        self._populate(client, oracle)
        want = self._audit_sig(oracle)
        driver.set_mesh(True, width=2)
        fault_plane.add(
            faults.MESH_DISPATCH_STALL,
            FaultRule(mode="hang", hang_s=10.0, count=1),
        )
        assert self._audit_sig(client) == want
        assert driver.mesh_layout() == 1, (
            "width 2 degrades to the single-device path"
        )
        fault_plane.release_hangs()
        time.sleep(0.3)
        fault_plane.clear(faults.MESH_DISPATCH_STALL)
        assert driver.breaker.probe_now()
        assert self._audit_sig(client) == want
        assert driver.last_sweep_stats.get("shards") == 1.0

    def test_watchdog_disabled_by_default(self):
        driver = TpuDriver()
        assert driver.mesh_watchdog_s == 0.0


class TestSnapshotQuarantine:
    """ISSUE 8 satellite: a snapshot that fails validation is moved
    aside into .quarantine/ EXACTLY once (with the outcome counter
    incremented), the cold path proceeds, and the next restart never
    re-validates it.  Read-mostly consumers (resync=False) never touch
    the shared dir."""

    def _written(self, snap_dir, n=6):
        kube = build_cluster(n=n)
        client = make_client(kube)
        sig, _ = audit_sig(client)
        assert Snapshotter(
            client, str(snap_dir), capture_delta=False
        ).write_once() is not None
        return kube, sig

    def _corrupt(self, snap_dir, fname, mutate):
        snap = os.path.join(
            str(snap_dir), snapfmt.list_snapshots(str(snap_dir))[0]
        )
        mutate(os.path.join(snap, fname))

    def _assert_quarantined_once(self, snap_dir, kube, cold_sig):
        qdir = os.path.join(str(snap_dir), snapfmt.QUARANTINE_DIR)
        before_q = outcome_counts().get("quarantined", 0)
        client = fresh_client()
        outcome = SnapshotLoader(str(snap_dir)).restore(client, kube)
        assert outcome == "fallback"
        assert outcome_counts().get("quarantined", 0) == before_q + 1
        # moved aside: the snapshot root holds no snap-* dirs anymore,
        # the quarantine dir holds exactly one
        assert snapfmt.list_snapshots(str(snap_dir)) == []
        assert len(os.listdir(qdir)) == 1
        # cold start proceeds to the oracle's verdicts
        client.add_template(SNAP_TEMPLATE)
        client.add_constraint(SNAP_CONSTRAINT)
        for obj in kube.list(("", "v1", "Namespace")):
            client.add_data(obj)
        sig, _ = audit_sig(client)
        assert sig == cold_sig
        # exactly once: the NEXT restore sees a clean (empty) root —
        # outcome none, no second quarantine sample
        second = SnapshotLoader(str(snap_dir)).restore(
            fresh_client(), kube
        )
        assert second == "none"
        assert outcome_counts().get("quarantined", 0) == before_q + 1
        assert len(os.listdir(qdir)) == 1

    def test_corrupt_manifest_is_quarantined_once(self, tmp_path):
        kube, sig = self._written(tmp_path)

        def mutate(path):
            blob = open(path).read()
            open(path, "w").write(blob.replace('"schema": 1', '"schema": 9'))

        self._corrupt(tmp_path, snapfmt.MANIFEST, mutate)
        self._assert_quarantined_once(tmp_path, kube, sig)

    def test_truncated_arrays_are_quarantined_once(self, tmp_path):
        kube, sig = self._written(tmp_path)

        def mutate(path):
            blob = open(path, "rb").read()
            open(path, "wb").write(blob[: max(1, len(blob) // 3)])

        self._corrupt(tmp_path, snapfmt.ARRAYS, mutate)
        self._assert_quarantined_once(tmp_path, kube, sig)

    def test_wrong_hmac_key_is_quarantined_once(self, tmp_path):
        kube, sig = self._written(tmp_path)

        def mutate(path):
            manifest = json.load(open(path))
            manifest["hmac"] = "f" * 64
            json.dump(manifest, open(path, "w"))

        self._corrupt(tmp_path, snapfmt.MANIFEST, mutate)
        self._assert_quarantined_once(tmp_path, kube, sig)

    def test_injected_corruption_point_quarantines(
        self, tmp_path, fault_plane
    ):
        """The seeded snapshot.corrupt fault point: post-seal payload
        validation fails -> the quarantine path, deterministically."""
        kube, sig = self._written(tmp_path)
        fault_plane.add(
            faults.SNAPSHOT_CORRUPT, FaultRule(mode="error", count=1)
        )
        self._assert_quarantined_once(tmp_path, kube, sig)

    def test_readmostly_consumer_never_quarantines(self, tmp_path):
        """A fleet replica adopting a SHARED dir (resync=False) must not
        move other processes' warmth aside, however corrupt — the dir's
        owner (the audit role) does that."""
        kube, _sig = self._written(tmp_path)

        def mutate(path):
            open(path, "w").write("{not json")

        self._corrupt(tmp_path, snapfmt.MANIFEST, mutate)
        listing = sorted(os.listdir(str(tmp_path)))
        before_q = outcome_counts().get("quarantined", 0)
        outcome = SnapshotLoader(str(tmp_path)).restore(
            fresh_client(), InMemoryKube(), resync=False
        )
        assert outcome == "fallback"
        assert sorted(os.listdir(str(tmp_path))) == listing
        assert outcome_counts().get("quarantined", 0) == before_q

    def test_older_snapshot_still_restores_after_quarantine(self, tmp_path):
        """Corrupt NEWEST + valid older: the owner quarantines the bad
        one and warm-restores from the older — quarantine never costs
        warmth that exists."""
        kube = build_cluster(n=6)
        client = make_client(kube)
        audit_sig(client)
        snapper = Snapshotter(client, str(tmp_path), capture_delta=False)
        first = snapper.write_once()
        snapper._last_write = 0.0
        second = snapper.write_once()
        assert first and second and first != second
        with open(os.path.join(second, snapfmt.MANIFEST), "w") as f:
            f.write("{not json")
        before_q = outcome_counts().get("quarantined", 0)
        outcome = SnapshotLoader(str(tmp_path)).restore(
            fresh_client(), kube
        )
        assert outcome == "restored"
        assert outcome_counts().get("quarantined", 0) == before_q + 1
        assert len(snapfmt.list_snapshots(str(tmp_path))) == 1


class TestDispatchGate:
    """The revocable mesh dispatch gate (parallel/mesh.py): revoke()
    unblocks the fleet from a wedged holder, and a waiter that was
    already parked on the revoked generation MIGRATES to the current one
    instead of dispatching under the abandoned lock (which would
    unserialize it against new-generation holders)."""

    def test_revoke_frees_new_acquirers_while_holder_wedged(self):
        from gatekeeper_tpu.parallel.mesh import DispatchGate

        gate = DispatchGate()
        held = gate.acquire()
        assert held is not None
        assert gate.acquire(timeout=0.05) is None  # busy
        gate.revoke()
        fresh = gate.acquire(timeout=1.0)
        assert fresh is not None, "revoked gate must admit new holders"
        gate.release(fresh)
        gate.release(held)  # the abandoned holder's late release: no-op

    def test_pre_revoke_waiter_migrates_to_current_generation(self):
        from gatekeeper_tpu.parallel.mesh import DispatchGate

        gate = DispatchGate()
        wedged = gate.acquire()
        order = []
        waiter_in = threading.Event()

        def old_gen_waiter():
            waiter_in.set()
            tok = gate.acquire()  # parks on the soon-revoked generation
            order.append("waiter")
            gate.release(tok)

        t = threading.Thread(target=old_gen_waiter, daemon=True)
        t.start()
        assert waiter_in.wait(2.0)
        time.sleep(0.05)  # let it block on the old lock
        gate.revoke()
        new_holder = gate.acquire(timeout=1.0)
        assert new_holder is not None
        # the wedged holder unsticks and releases the OLD lock: the
        # waiter wakes, must NOT proceed (stale generation) while the
        # new generation is held
        gate.release(wedged)
        time.sleep(0.15)
        assert order == [], (
            "waiter ran under the abandoned generation, unserialized "
            "against the new-generation holder"
        )
        order.append("new-holder-done")
        gate.release(new_holder)
        t.join(timeout=2.0)
        assert not t.is_alive()
        assert order == ["new-holder-done", "waiter"]


class TestOverloadStorm:
    """ISSUE 12: shedding under storm load never corrupts verdicts —
    accepted requests match the interpreter oracle exactly while the
    overload plane refuses the excess, and the new fault points drive
    the storm deterministically."""

    def test_zero_verdict_divergence_while_shedding(self, fault_plane):
        """Saturate a REAL evaluation pipeline (slow dispatch via an
        injected latency, bounded pending queue): every shed is an
        OverloadShed, every accepted verdict is byte-identical to the
        interpreter oracle — shedding must drop requests, never
        accuracy."""
        client, driver = tpu_client()
        oracle = interp_client()
        mb = MicroBatcher(client, window_s=0.005, max_pending=2,
                          adaptive=False)
        fault_plane.add(
            faults.TPU_DISPATCH,
            FaultRule(mode="latency", latency_s=0.15),
        )
        reqs = [
            ns_review(f"storm-{i}",
                      labels={"gatekeeper": "on"} if i % 3 else None)
            for i in range(12)
        ]
        want = {
            r["name"]: review_sig(oracle.review(
                AugmentedReview(admission_request=r)))
            for r in reqs
        }
        got: dict = {}
        sheds: list = []
        lock = threading.Lock()

        def call(req):
            try:
                resp = mb.review(AugmentedReview(admission_request=req))
            except deadline.OverloadShed:
                with lock:
                    sheds.append(req["name"])
                return
            with lock:
                got[req["name"]] = review_sig(resp)

        threads = [threading.Thread(target=call, args=(r,)) for r in reqs]
        try:
            for t in threads:
                t.start()
                time.sleep(0.01)
            for t in threads:
                t.join(timeout=30)
            assert sheds, "the storm never forced a shed — not a storm"
            assert got, "everything shed — no accepted verdicts to check"
            divergences = [
                name for name, sig in got.items() if sig != want[name]
            ]
            assert divergences == [], (
                f"accepted verdicts diverged under shedding: {divergences}"
            )
        finally:
            mb.stop()

    def test_overload_storm_point_drives_door_sheds(self, fault_plane):
        """The fleet.overload_storm seam: a latency rule holds a proxied
        attempt with its inflight slot taken, so the door's arrival-time
        shed engages — the requests that arrived behind it are refused
        with the explicit verdict while the slow one completes
        correctly.  The rule stalls the reactor itself, so a shed is
        fast by the DOOR's clock (its wire trace), not the client's."""
        from gatekeeper_tpu.fleet import EventFrontDoor
        from gatekeeper_tpu.obs import trace as obstrace
        from tests.wirestub import StubWire, post, wait_until

        obstrace.configure(buffer_size=256, sample_rate=1.0)
        backend = StubWire(name="b")
        fault_plane.add(
            faults.OVERLOAD_STORM,
            FaultRule(mode="latency", latency_s=0.4),
        )
        door = EventFrontDoor(
            [backend.backend()], probe_interval_s=3600.0, max_inflight=1,
        ).start()
        body = json.dumps({"request": ns_review("storm")}).encode()
        results: list = []
        lock = threading.Lock()

        def one():
            st, hd, data = post(door.port, body)
            with lock:
                results.append((st, hd.get("X-GK-Trace-Id"), data))

        threads = [threading.Thread(target=one) for _ in range(6)]
        try:
            for t in threads:
                t.start()
                time.sleep(0.02)
            for t in threads:
                t.join(timeout=30)
            assert len(results) == 6
            served = [d for c, _t, d in results if c == 200]
            assert served, "the storm starved every request"
            assert all(json.loads(d)["served_by"] == "b" for d in served)
            shed = [(t, d) for c, t, d in results if c == 429]
            assert shed, "inflight bound never shed under the storm"

            def door_ms(tid):
                return next(
                    (t["duration_ms"]
                     for t in obstrace.get_tracer().traces()
                     if t["trace_id"] == tid), None)

            for tid, data in shed:
                out = json.loads(data)["response"]
                assert out["allowed"] is False
                assert out["status"]["code"] == 429
                assert wait_until(lambda: door_ms(tid) is not None)
                assert door_ms(tid) < 200.0, \
                    f"shed took {door_ms(tid):.1f} ms at the door"
        finally:
            door.stop()
            backend.stop()

    def test_slow_client_point_fires_in_the_inbound_read(self, fault_plane):
        """The frontdoor.slow_client seam on the door that serves: a
        latency rule holds the reactor's inbound read (a trickling
        client's shape) without corrupting the response."""
        from gatekeeper_tpu.fleet import EventFrontDoor
        from tests.wirestub import StubWire, post

        backend = StubWire(name="b")
        fault_plane.add(
            faults.SLOW_CLIENT,
            FaultRule(mode="latency", latency_s=0.25, count=1),
        )
        door = EventFrontDoor(
            [backend.backend()], probe_interval_s=3600.0,
        ).start()
        try:
            t0 = time.perf_counter()
            st, _hd, data = post(door.port)
            dur = time.perf_counter() - t0
            assert st == 200 and json.loads(data)["served_by"] == "b"
            assert dur >= 0.25, "the slow-client latency never applied"
            # the rule is spent: the next request is not held
            t0 = time.perf_counter()
            assert post(door.port)[0] == 200
            assert time.perf_counter() - t0 < 0.25
        finally:
            door.stop()
            backend.stop()

    def test_slow_client_error_rule_drops_only_that_connection(
            self, fault_plane):
        """An error rule at the seam is a client connection the door
        gives up on: it closes, the door keeps serving."""
        import http.client

        from gatekeeper_tpu.fleet import EventFrontDoor
        from tests.wirestub import StubWire, post

        backend = StubWire(name="b")
        fault_plane.add(
            faults.SLOW_CLIENT, FaultRule(mode="error", count=1),
        )
        door = EventFrontDoor(
            [backend.backend()], probe_interval_s=3600.0,
        ).start()
        try:
            with pytest.raises((http.client.HTTPException, OSError)):
                post(door.port)
            assert backend.records == []  # nothing was proxied
            assert post(door.port)[0] == 200
        finally:
            door.stop()
            backend.stop()
