"""Front-door resilience (ISSUE 8): the bounded single retry onto a
different live backend, health-based ejection, probing readmission, and
the supervisor's backend-swap hook — plus the ISSUE 11 wire-path
observability contract (correlation headers on EVERY path, /fleetz
latency summaries, stage and request metrics) and the ISSUE 12 overload
plane.  All against stub wire backends (tests/wirestub.py) — no replica
spawn, so this runs everywhere tier-1 does.  The refusal taxonomy and
the pipelining contract are tests/test_event_edge.py's; the control
plane without a socket is tests/test_roster.py's."""

import http.client
import json
import socket
import time

import pytest

from gatekeeper_tpu.fleet.evdoor import EventFrontDoor
from gatekeeper_tpu.fleet.roster import ROUND_ROBIN, Roster
from gatekeeper_tpu.fleet.wireproto import WIRE_STAGES
from gatekeeper_tpu.metrics.views import global_registry
from gatekeeper_tpu.obs import trace as obstrace
from tests.wirestub import ReadyStub, StubWire, free_port, get, post, \
    wait_until

ADMIT_BODY = json.dumps({"request": {"uid": "uid-overload"}}).encode()


def _dead(replica_id: str) -> dict:
    return {"host": "127.0.0.1", "port": free_port(),
            "replica_id": replica_id}


def _served_by(resp_body: bytes) -> str:
    return json.loads(resp_body)["served_by"]


@pytest.fixture()
def live_backend():
    stub = StubWire(name="live")
    yield stub
    stub.stop()


class TestBoundedRetry:
    def test_refused_backend_retries_once_onto_live(self, live_backend):
        """The satellite regression: a refused backend connection must be
        retried (exactly once) on a DIFFERENT live backend — never a 502
        while a live backend exists."""
        door = EventFrontDoor(
            [_dead("dead"), live_backend.backend()],
            policy=ROUND_ROBIN, probe_interval_s=3600.0,
        ).start()
        try:
            for _ in range(6):
                st, hd, body = post(door.port)
                assert st == 200
                assert _served_by(body) == "live"
                assert hd.get("X-GK-Replica") == "live"
            stats = door.stats()
            by_id = {b["replica_id"]: b for b in stats["backends"]}
            assert by_id["live"]["served"] == 6
            # the refused backend was ejected on its FIRST refusal, so
            # later requests never even tried it
            assert by_id["dead"]["ejected"] is True
            assert by_id["dead"]["errors"] <= 2
            assert stats["retries"] >= 1
        finally:
            door.stop()

    def test_retry_is_bounded_to_one(self, live_backend):
        """Three dead backends + one live under round robin: a request
        whose first AND second choices are dead must 502 (the retry
        budget is one), until ejection converges the live set."""
        door = EventFrontDoor(
            [_dead(f"dead{i}") for i in range(3)]
            + [live_backend.backend()],
            policy=ROUND_ROBIN, probe_interval_s=3600.0,
        ).start()
        try:
            codes = [post(door.port)[0] for _ in range(8)]
            assert 502 in codes or all(c == 200 for c in codes)
            # ejection converges: once the dead trio is ejected, every
            # request lands on the live backend directly
            assert wait_until(lambda: all(
                b["ejected"] for b in door.stats()["backends"]
                if b["replica_id"].startswith("dead")
            ))
            assert all(post(door.port)[0] == 200 for _ in range(4))
        finally:
            door.stop()

    def test_empty_retry_bucket_denies_the_retry(self, live_backend):
        """Two dead backends ahead of a live one under round robin with
        a zero-capacity retry budget: the first request's failure CANNOT
        be retried — explicit 502 even though a live backend exists."""
        door = EventFrontDoor(
            [_dead("dead0"), _dead("dead1"), live_backend.backend()],
            policy=ROUND_ROBIN, probe_interval_s=3600.0,
            retry_budget_cap=0.0, retry_budget_rate_per_s=0.0,
        ).start()
        try:
            codes = [post(door.port, ADMIT_BODY)[0] for _ in range(6)]
            assert 502 in codes
            assert door.retry_budget.denied >= 1
            assert door.stats()["retry_budget"]["denied"] >= 1
            # the dead pair still ejects on refusal, so the door
            # converges onto the live backend WITHOUT retries
            assert wait_until(lambda: all(
                b["ejected"] for b in door.stats()["backends"]
                if b["replica_id"].startswith("dead")))
            assert post(door.port, ADMIT_BODY)[0] == 200
            # a denied retry gave its reservation back
            assert all(b["inflight"] == 0
                       for b in door.stats()["backends"])
        finally:
            door.stop()


class TestWireObservability:
    """ISSUE 11: the door originates a W3C trace per request, injects
    traceparent downstream, stamps correlation headers on every path,
    and summarizes per-backend latency on /fleetz."""

    def test_caller_traceparent_adopted_and_reinjected(self, live_backend):
        door = EventFrontDoor([live_backend.backend()],
                              probe_interval_s=3600.0).start()
        try:
            caller_tid = "ab" * 16
            _st, hd, _body = post(door.port, headers={
                "traceparent": f"00-{caller_tid}-{'12' * 8}-01"})
            # the caller's trace id is adopted...
            assert hd["X-GK-Trace-Id"] == caller_tid
            # ...and re-injected downstream with the DOOR's span id,
            # not the caller's (the replica must parent to the door)
            seen = live_backend.records[-1].traceparent
            assert seen and caller_tid in seen
            assert "12" * 8 not in seen
        finally:
            door.stop()

    def test_correlation_headers_on_error_paths(self):
        """The satellite regression: 502/all-down and bad-request
        responses must carry the trace id (and the last-tried backend)
        too — an unattributable 502 is unactionable."""
        door = EventFrontDoor([_dead("dead")],
                              probe_interval_s=3600.0).start()
        try:
            st, hd, _body = post(door.port)
            assert st == 502
            assert hd.get("X-GK-Trace-Id")
            assert hd.get("X-GK-Replica") == "dead"
            # bad framing: trace id still present
            conn = http.client.HTTPConnection(
                "127.0.0.1", door.port, timeout=10)
            conn.request("POST", "/v1/admit", body=b"{}",
                         headers={"Content-Length": "nope"})
            r = conn.getresponse()
            hd = dict(r.getheaders())
            r.read()
            conn.close()
            assert r.status == 400
            assert hd.get("X-GK-Trace-Id")
        finally:
            door.stop()

    def test_stage_and_request_metrics_recorded(self, live_backend):
        obstrace.configure(buffer_size=256, sample_rate=1.0)
        door = EventFrontDoor([live_backend.backend()],
                              probe_interval_s=3600.0).start()
        try:
            reqs_before = dict(global_registry().view_rows(
                "frontdoor_requests_total"))
            assert post(door.port)[0] == 200

            def stages_seen():
                return {k[0] for k in global_registry().view_rows(
                    "frontdoor_stage_seconds")}

            assert wait_until(
                lambda: set(WIRE_STAGES) <= stages_seen()
            ), stages_seen()
            key = ("ok", "live")

            def counted():
                # outcomes flush with the reactor's next tick
                return global_registry().view_rows(
                    "frontdoor_requests_total").get(key, 0)

            assert wait_until(
                lambda: counted() == reqs_before.get(key, 0) + 1)
        finally:
            door.stop()

    def test_fleetz_latency_summary(self, live_backend):
        door = EventFrontDoor([live_backend.backend()],
                              probe_interval_s=3600.0).start()
        try:
            for _ in range(5):
                assert post(door.port)[0] == 200
            stats = json.loads(get(door.port, "/fleetz")[1])
            lat = stats["backends"][0]["latency"]
            assert lat["n"] == 5
            assert lat["p50_ms"] is not None
            assert lat["p99_ms"] >= lat["p50_ms"]
            assert lat["window_s"] == Roster.LATENCY_WINDOW_S
        finally:
            door.stop()

    def test_door_serves_metrics_and_debug(self, live_backend):
        obstrace.configure(buffer_size=256, sample_rate=1.0)
        door = EventFrontDoor([live_backend.backend()],
                              probe_interval_s=3600.0).start()
        try:
            assert post(door.port)[0] == 200
            body = get(door.port, "/metrics")[1].decode()
            assert "gatekeeper_frontdoor_stage_seconds" in body
            assert "# EOF" not in body

            def ring_traces():
                st, data = get(door.port, "/debug/traces?min_ms=0")
                assert st == 200
                return json.loads(data)["traces"]

            assert wait_until(lambda: bool(ring_traces()))
        finally:
            door.stop()


class TestEjectionReadmission:
    def test_dead_backend_readmitted_when_it_returns(self, live_backend):
        port, ready_port = free_port(), free_port()
        door = EventFrontDoor(
            [{"host": "127.0.0.1", "port": port,
              "probe_port": ready_port, "replica_id": "flappy"},
             live_backend.backend()],
            policy=ROUND_ROBIN, probe_interval_s=0.05,
        ).start()
        revived = ready = None
        try:
            post(door.port)  # trips the refused->eject path
            assert wait_until(
                lambda: door.stats()["backends"][0]["ejected"]
            )
            # the replica comes back on the SAME ports: the prober's
            # /readyz GET readmits it
            revived = StubWire(name="flappy", port=port)
            ready = ReadyStub(port=ready_port)
            assert wait_until(
                lambda: not door.stats()["backends"][0]["ejected"]
            ), "prober never readmitted the revived backend"
            served = {_served_by(post(door.port)[2]) for _ in range(8)}
            assert served == {"flappy", "live"}
        finally:
            door.stop()
            if revived is not None:
                revived.stop()
            if ready is not None:
                ready.stop()

    def test_set_backend_repoints_and_readmits(self, live_backend):
        """The supervisor's restart hook: the replica comes back on a
        fresh ephemeral port; set_backend re-points the named entry."""
        door = EventFrontDoor([_dead("r0")],
                              probe_interval_s=3600.0).start()
        try:
            assert post(door.port)[0] == 502
            assert door.set_backend(
                "r0", "127.0.0.1", live_backend.port) is True
            st, _hd, body = post(door.port)
            assert st == 200
            assert _served_by(body) == "live"
            b = door.stats()["backends"][0]
            assert b["port"] == live_backend.port
            assert b["ejected"] is False
            assert door.set_backend("nope", "127.0.0.1", 1) is False
        finally:
            door.stop()

    def test_suspend_takes_backend_out_of_rotation(self, live_backend):
        second = StubWire(name="b")
        door = EventFrontDoor(
            [live_backend.backend(), second.backend()],
            policy=ROUND_ROBIN, probe_interval_s=3600.0,
        ).start()
        try:
            assert door.suspend("b") is True
            served = {_served_by(post(door.port)[2]) for _ in range(6)}
            assert served == {"live"}
            assert door.suspend("ghost") is False
        finally:
            door.stop()
            second.stop()

    def test_healthz_counts_ejected_backends_dead(self):
        door = EventFrontDoor(
            [("127.0.0.1", free_port())], probe_interval_s=3600.0,
        ).start()
        try:
            post(door.port)  # refused -> ejected
            assert get(door.port, "/healthz")[0] == 503
        finally:
            door.stop()


class TestDeadlinePropagation:
    """ISSUE 12: the door derives min(own budget, caller header), sends
    the REMAINING milliseconds downstream (test_event_edge.py reads them
    off the wire record), and answers expired work with the explicit
    fail-open/closed verdict."""

    def test_slow_backend_with_tight_budget_expires_in_budget(self):
        """The deadline timer firing on an exhausted budget answers the
        explicit expired verdict within ~budget — never a parked
        socket.  ONE expiry charges the error streak (a backend timing
        out every request is indistinguishable from wedged) but does
        not eject; the next success clears it."""
        slow = StubWire(mode="gate", name="slow")
        door = EventFrontDoor(
            [slow.backend()],
            probe_interval_s=3600.0, admission_budget_s=0.3,
        ).start()
        try:
            t0 = time.perf_counter()
            st, _hd, body = post(door.port, ADMIT_BODY)
            dur = time.perf_counter() - t0
            assert st == 200
            out = json.loads(body)["response"]
            assert out["allowed"] is False
            assert out["status"]["code"] == 504
            assert dur < 2.0, f"expired answer took {dur:.3f}s"
            b = door.stats()["backends"][0]
            assert b["consecutive_errors"] == 1
            assert b["ejected"] is False  # one expiry is forgivable
            # a served request clears the streak: a healthy backend
            # that occasionally carries a too-tight request never
            # accumulates toward ejection
            slow.gate.set()
            st2, _hd2, _b2 = post(door.port, ADMIT_BODY)
            assert st2 == 200
            assert door.stats()["backends"][0]["consecutive_errors"] == 0
        finally:
            door.stop()
            slow.stop()

    def test_wedged_backend_ejects_under_deadline_timeouts(self):
        """A backend that times out EVERY budgeted request is wedged
        from the door's perspective and must eject like any failing
        backend — never-ejecting would leave it burning half of all
        request budgets forever; a falsely-ejected healthy one is
        readmitted by the /readyz prober."""
        wedged = StubWire(mode="hang", name="wedged")
        door = EventFrontDoor(
            [wedged.backend()],
            probe_interval_s=3600.0, admission_budget_s=0.2,
        ).start()
        try:
            for _ in range(Roster.EJECT_ERROR_STREAK):
                st, _hd, body = post(door.port, ADMIT_BODY)
                assert st == 200
                assert json.loads(body)["response"]["status"]["code"] \
                    == 504
            assert door.stats()["backends"][0]["ejected"] is True
        finally:
            door.stop()
            wedged.stop()


class TestInflightShed:
    def test_no_bound_means_no_shed(self, live_backend):
        door = EventFrontDoor([live_backend.backend()],
                              probe_interval_s=3600.0).start()
        try:
            assert door.roster.has_capacity() is True
            st, _hd, _body = post(door.port, ADMIT_BODY)
            assert st == 200 and door.sheds == 0
        finally:
            door.stop()


class TestSlowClientHardening:
    def test_slowloris_header_stall_is_closed_by_timeout(self):
        door = EventFrontDoor(
            [("127.0.0.1", free_port())],
            probe_interval_s=3600.0, header_timeout_s=0.3,
        ).start()
        try:
            s = socket.create_connection(("127.0.0.1", door.port),
                                         timeout=5)
            s.sendall(b"POST /v1/admit HTTP/1.1\r\nHost: x\r\n")
            # ...and never finish the headers: the sweep must close the
            # connection instead of holding it forever
            s.settimeout(5.0)
            t0 = time.perf_counter()
            data = s.recv(1024)
            dur = time.perf_counter() - t0
            s.close()
            assert data == b""  # server closed on us
            assert dur < 3.0, f"slowloris was held {dur:.1f}s"
        finally:
            door.stop()

    def test_stalled_body_answers_408(self):
        door = EventFrontDoor(
            [("127.0.0.1", free_port())],
            probe_interval_s=3600.0, header_timeout_s=0.3,
        ).start()
        try:
            s = socket.create_connection(("127.0.0.1", door.port),
                                         timeout=5)
            s.sendall(b"POST /v1/admit HTTP/1.1\r\nHost: x\r\n"
                      b"Content-Length: 100\r\n\r\nonly-a-bit")
            s.settimeout(5.0)
            chunks = []
            try:
                while True:
                    got = s.recv(4096)
                    if not got:
                        break
                    chunks.append(got)
            except socket.timeout:
                pass
            s.close()
            assert b"408" in b"".join(chunks)
        finally:
            door.stop()

    def test_oversized_body_answers_413_without_reading(
            self, live_backend):
        door = EventFrontDoor([live_backend.backend()],
                              probe_interval_s=3600.0).start()
        try:
            s = socket.create_connection(("127.0.0.1", door.port),
                                         timeout=5)
            huge = EventFrontDoor.MAX_BODY + 1
            s.sendall(f"POST /v1/admit HTTP/1.1\r\nHost: x\r\n"
                      f"Content-Length: {huge}\r\n\r\n".encode())
            s.settimeout(5.0)
            data = s.recv(4096)
            s.close()
            assert b"413" in data.split(b"\r\n", 1)[0]
        finally:
            door.stop()
