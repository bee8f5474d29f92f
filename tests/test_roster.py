"""The front door's control plane without a client socket
(gatekeeper_tpu/fleet/roster.py): the locked inflight reservation, the
balancing policies, ejection and readmission streaks, the /readyz
probe, the retry token bucket — and the seam itself: the fleet package
has one door, and the replica side imports nothing from it."""

import ast
import pathlib
import sys
import threading

import pytest

import gatekeeper_tpu.fleet as fleet
from gatekeeper_tpu.deadline import OverloadShed
from gatekeeper_tpu.fleet.roster import LEAST_INFLIGHT, ROUND_ROBIN, \
    RetryBudget, Roster
from tests.wirestub import ReadyStub, free_port, wait_until


def _roster(n=1, **kw):
    return Roster(
        [{"host": "127.0.0.1", "port": 1000 + i, "replica_id": f"r{i}"}
         for i in range(n)], **kw)


class TestInflightReservation:
    """The max_inflight bound is enforced by RESERVATION in choose()
    (slot taken under the backend's lock), not by a check-then-act
    read: concurrent callers cannot overshoot the bound, and a
    saturated-but-live fleet raises OverloadShed instead of silently
    falling through to a saturated backend."""

    def test_choose_reserves_and_sheds_at_the_bound(self):
        roster = _roster(max_inflight=2)
        b1 = roster.choose()
        b2 = roster.choose()
        assert b1 is b2 and b1.inflight == 2  # both slots reserved
        assert roster.has_capacity() is False
        with pytest.raises(OverloadShed):
            roster.choose()  # must shed, not overshoot
        # releasing one reservation makes the slot choosable again
        roster.release(b1)
        assert roster.has_capacity() is True
        assert roster.choose() is b1 and b1.inflight == 2

    def test_concurrent_chooses_never_overshoot(self):
        roster = _roster(max_inflight=3)
        granted, shed = [], []
        lock = threading.Lock()
        start = threading.Barrier(16)

        def race():
            start.wait()
            try:
                b = roster.choose()
            except OverloadShed:
                with lock:
                    shed.append(1)
                return
            with lock:
                granted.append(b)

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            ts = [threading.Thread(target=race) for _ in range(16)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=10)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in ts)
        assert len(granted) == 3 and len(shed) == 13
        assert roster.backends[0].inflight == 3  # exactly the bound

    def test_release_after_a_lost_client_charges_nothing(self):
        """A client that left mid-flight gives its slot back without an
        error charge: the replica did nothing wrong."""
        roster = _roster(max_inflight=1)
        b = roster.choose()
        roster.release(b)
        assert (b.inflight, b.errors, b.consecutive_errors) == (0, 0, 0)
        assert b.ejected is False
        assert roster.choose() is b  # the freed slot admits the next

    def test_the_bound_does_not_apply_to_the_fail_static_probe(self):
        """Every backend ejected: choose() hands one out anyway (its
        success readmits it) and ignores the bound — with zero live
        capacity the alternative is refusing everything."""
        roster = _roster(max_inflight=1)
        b = roster.backends[0]
        roster.eject(b, "test")
        assert roster.has_capacity() is True
        assert roster.choose() is b and roster.choose() is b
        assert b.inflight == 2


class TestChoice:
    def test_round_robin_rotates_over_live_backends(self):
        roster = _roster(3, policy=ROUND_ROBIN)
        picks = [roster.choose().replica_id for _ in range(6)]
        assert sorted(picks) == ["r0", "r0", "r1", "r1", "r2", "r2"]
        roster.eject(roster.backends[1], "test")
        picks = {roster.choose().replica_id for _ in range(6)}
        assert picks == {"r0", "r2"}

    def test_least_inflight_prefers_the_idle_backend(self):
        roster = _roster(2, policy=LEAST_INFLIGHT)
        busy, idle = roster.backends
        busy.inflight = 5
        assert all(roster.choose() is idle for _ in range(4))
        assert idle.inflight == 4

    @pytest.mark.parametrize("policy", [ROUND_ROBIN, LEAST_INFLIGHT])
    def test_exclude_is_honoured_until_nothing_is_left(self, policy):
        """A retry goes to a DIFFERENT backend; with every backend
        tried, nothing is choosable."""
        roster = _roster(2, policy=policy)
        first = roster.choose()
        second = roster.choose(exclude={first})
        assert second is not first
        assert roster.choose(exclude={first, second}) is None

    def test_rejects_unknown_policy_and_empty_backends(self):
        with pytest.raises(ValueError):
            Roster([("127.0.0.1", 1)], policy="weighted")
        with pytest.raises(ValueError):
            Roster([])

    def test_pairs_and_dicts_both_name_a_backend(self):
        roster = Roster([("127.0.0.1", 7),
                         {"port": 8, "probe_port": 9, "replica_id": "x"}])
        a, b = roster.backends
        assert (a.replica_id, a.port, a.probe_port) == ("127.0.0.1:7", 7, 0)
        assert (b.replica_id, b.host, b.probe_port) == ("x", "127.0.0.1", 9)


class TestEjectionReadmission:
    @pytest.mark.parametrize("exc, failures", [
        (ConnectionRefusedError(), 1),   # nothing listening: dead NOW
        (ConnectionResetError(), Roster.EJECT_ERROR_STREAK),
        (None, Roster.EJECT_ERROR_STREAK),   # deadline expiries
    ], ids=["refused", "streak", "expiries"])
    def test_eject_on_refused_or_on_streak(self, exc, failures):
        roster = _roster()
        b = roster.backends[0]
        for k in range(failures):
            assert b.ejected is False, f"ejected after {k} failures"
            roster.choose()
            roster.failed(b, exc)
        assert b.ejected is True
        assert (b.inflight, b.errors) == (0, failures)
        assert roster.live_count() == 0

    def test_a_success_clears_the_streak(self):
        roster = _roster()
        b = roster.backends[0]
        for _ in range(Roster.EJECT_ERROR_STREAK - 1):
            roster.choose()
            roster.failed(b, TimeoutError())
        roster.choose()
        roster.served(b, [1.5], live=True)
        assert (b.consecutive_errors, b.served, b.inflight) == (0, 1, 0)
        roster.choose()
        roster.failed(b, TimeoutError())
        assert b.ejected is False  # the streak started over

    @pytest.mark.parametrize("live", [True, False])
    def test_serving_while_ejected_readmits_unless_draining(self, live):
        """The fail-static probe proved the backend back — unless every
        answer was a 503: a draining replica answering honestly must
        NOT re-enter rotation."""
        roster = _roster()
        b = roster.backends[0]
        roster.eject(b, "test")
        roster.choose()
        roster.served(b, [2.0], live=live)
        assert b.ejected is (not live)
        assert b.readmissions == (1 if live else 0)

    def test_liveness_is_recent_not_sticky(self):
        roster = _roster()
        b = roster.backends[0]
        roster.choose()
        roster.served(b, [1.0], live=True)
        assert roster.live_count() == 1
        b.consecutive_errors = Roster.LIVE_ERROR_STREAK
        assert roster.live_count() == 0  # served > 0 does not count

    def test_set_backend_repoints_and_readmits(self):
        roster = _roster()
        b = roster.backends[0]
        roster.choose()
        roster.failed(b, ConnectionRefusedError())
        assert b.ejected is True
        assert roster.set_backend("r0", "10.0.0.9", 4242) is True
        assert (b.host, b.port, b.ejected, b.consecutive_errors) == \
            ("10.0.0.9", 4242, False, 0)
        assert roster.stats()[0]["port"] == 4242
        assert roster.set_backend("nope", "127.0.0.1", 1) is False

    def test_suspend_ejects_by_name(self):
        roster = _roster(2, policy=ROUND_ROBIN)
        assert roster.suspend("r1") is True
        assert roster.find("r1").ejected is True
        assert {roster.choose().replica_id for _ in range(4)} == {"r0"}
        assert roster.suspend("ghost") is False
        assert roster.find("ghost") is None

    def test_probe_readmits_when_readyz_answers(self):
        port = free_port()
        roster = Roster([{"host": "127.0.0.1", "port": 1,
                          "probe_port": port, "replica_id": "r0"}])
        b = roster.backends[0]
        roster.eject(b, "test")
        roster.probe_once()       # nothing listening: still down
        assert b.ejected is True
        ready = ReadyStub(port=port)
        try:
            roster.probe_once()
            assert b.ejected is False and b.readmissions == 1
        finally:
            ready.stop()

    def test_prober_thread_starts_once_and_stops(self):
        port = free_port()
        roster = Roster([{"host": "127.0.0.1", "port": 1,
                          "probe_port": port, "replica_id": "r0"}],
                        probe_interval_s=0.02)
        ready = ReadyStub(port=port)
        roster.start()
        roster.start()   # idempotent: no second prober
        try:
            assert sum(t.name == "evdoor-probe"
                       for t in threading.enumerate()) == 1
            roster.eject(roster.backends[0], "test")
            assert wait_until(lambda: not roster.backends[0].ejected)
        finally:
            roster.stop()
            ready.stop()
        assert not any(t.name == "evdoor-probe"
                       for t in threading.enumerate())


class TestRetryBudget:
    def test_bucket_refills_and_grants_again(self):
        rb = RetryBudget(cap=2.0, rate_per_s=1000.0)
        assert rb.take() and rb.take()
        # cap 2, both taken; at 1000/s the bucket refills immediately
        assert wait_until(rb.take, timeout_s=1.0)

    def test_deny_then_starve(self):
        rb = RetryBudget(cap=1.0, rate_per_s=0.0)
        assert rb.take()
        assert not rb.take()
        assert rb.denied == 1
        assert rb.tokens() == 0.0


class TestOneDoor:
    def test_the_fleet_package_has_one_door(self):
        assert "FrontDoor" not in fleet.__all__
        assert not hasattr(fleet, "FrontDoor")
        assert not hasattr(fleet, "frontdoor")
        assert fleet.EventFrontDoor.__mro__[1] is object

    def test_the_replica_side_imports_no_door_module(self):
        """wirelistener runs in the replica: what it shares with the
        door comes from wireproto, never from the door's module."""
        src = pathlib.Path(fleet.__file__).with_name("wirelistener.py")
        imported = set()
        for node in ast.walk(ast.parse(src.read_text())):
            if isinstance(node, ast.ImportFrom):
                imported.add(node.module or "")
                imported.update(a.name for a in node.names)
            elif isinstance(node, ast.Import):
                imported.update(a.name for a in node.names)
        assert not {m for m in imported
                    if m.split(".")[-1] in ("evdoor", "frontdoor",
                                            "roster")}
