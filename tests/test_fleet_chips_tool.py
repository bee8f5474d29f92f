"""Tier-1 wiring for tools/check_fleet_chips.py: off a TPU the tool runs
two supervised CPU replicas through a SIGKILL and a restart in place
with nothing placed; what it says of a PLACED fleet (a chip each, a
restart back on its slot's chip) is held against a stand-in supervisor,
since four chips are the chip machine's (PERF.md section 6, PR 36)."""

import os
import sys
import types

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import check_fleet_chips as chk  # noqa: E402

from .test_snapshot_concurrent import spawn_available


@spawn_available
def test_off_a_tpu_two_replicas_restart_in_place_and_nothing_is_placed():
    summary = {}
    assert chk.run_checks(summary) == []
    assert summary["placed"] is False and summary["replicas"] == 2
    assert summary["chips_held"] == {"r0": None, "r1": None}
    r = summary["restarted"]
    assert (r["replica_id"], r["index"], r["chip"]) == ("r1", 1, None)
    assert r["pid"] != r["pid_was"]


class FakeSupervisor:
    """start(n) gives replica i chip `first[i]`; killing one 'restarts'
    it at once on chip `again[i]`."""

    first = again = ()

    def __init__(self, **kw):
        self.kw = kw
        self.live = []

    def _handle(self, i, chip, pid):
        return types.SimpleNamespace(
            replica_id=f"r{i}", index=i, ready={"chip": chip},
            proc=types.SimpleNamespace(pid=pid))

    def start(self, n):
        assert self.kw["chips"] == n and self.kw["env"] is None
        self.live = [self._handle(i, self.first[i], os.getpid())
                     for i in range(n)]
        return list(self.live)

    def status(self):
        # by the first poll the victim is "back"
        self.live[1] = self._handle(1, self.again[1], -1)
        return {"r1": {"state": "running", "restarts": 1}}

    def handles(self):
        return list(self.live)

    def stop(self):
        pass


@pytest.mark.parametrize("first, again, finding", [
    ((0, 1, 2, 3), (0, 1, 2, 3), None),
    ((0, 1, 1, 3), (0, 1, 1, 3), "do not hold a chip each"),
    ((0, None, 2, 3), (0, None, 2, 3), "do not hold a chip each"),
    ((0, 1, 2, 3), (0, 2, 2, 3), "restarted on chip 2, held 1 before"),
])
def test_what_it_says_of_a_placed_fleet(monkeypatch, first, again, finding):
    import gatekeeper_tpu.fleet.supervisor as sup_mod
    import gatekeeper_tpu.util.chips as chips_mod

    FakeSupervisor.first, FakeSupervisor.again = first, again
    monkeypatch.setattr(sup_mod, "ReplicaSupervisor", FakeSupervisor)
    monkeypatch.setattr(chips_mod, "host_chips", lambda: 4)
    monkeypatch.setattr(chk.os, "kill", lambda pid, sig: None)
    summary = {}
    problems = chk.run_checks(summary)
    assert summary["placed"] is True and summary["replicas"] == 4
    if finding is None:
        assert problems == []
        assert summary["restarted"]["chip"] == 1
    else:
        assert problems and finding in problems[0]
