"""Chip placement where replicas are spawned (ISSUE 36, docs/fleet.md
"Chips"): `placement_env` is pure and jax-free; a launch with one chip
builds the environment and the argv it always built, byte for byte; the
supervisor puts a restarted replica back on its slot's chip; and the
roster says how each least_inflight choice fell."""

import json
import os
import subprocess
import sys
import threading

import pytest

from gatekeeper_tpu.fleet import replica as rep
from gatekeeper_tpu.fleet import supervisor as sup_mod
from gatekeeper_tpu.fleet.placement import placement_env
from gatekeeper_tpu.fleet.roster import Roster
from gatekeeper_tpu.util import chips as chips_mod

from .test_snapshot_concurrent import spawn_available
from .test_supervisor import FakeSpawner, make_supervisor, wait_until


# ---- placement_env ----------------------------------------------------------


@pytest.mark.parametrize("chips", [0, 1])
def test_one_chip_or_none_places_nothing(chips):
    assert [placement_env(i, chips) for i in range(5)] == [{}] * 5


def test_four_chips_give_four_replicas_a_chip_each():
    envs = [placement_env(i, 4) for i in range(8)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == list("01230123")
    for e in envs:
        # one chip, alone, no peers looked for (docs/fleet.md says which
        # line does what), and nothing the chip machine did not need
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert set(e) == {"TPU_VISIBLE_CHIPS", "TPU_PROCESS_BOUNDS",
                          "TPU_CHIPS_PER_PROCESS_BOUNDS"}
        assert all(isinstance(v, str) for v in e.values())


def test_placement_is_importable_without_jax():
    """The harness's parent and every launcher stay off jax: a parent
    that has touched it holds the chip its children need."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "from gatekeeper_tpu.fleet.placement import placement_env\n"
         "from gatekeeper_tpu.util.chips import held_chips\n"
         "placement_env(1, 4); held_chips()\n"
         "print('jax' in sys.modules)"],
        cwd=rep.REPO_ROOT, capture_output=True, text=True, timeout=60)
    assert out.stdout.strip() == "False", out.stderr[-500:]


def test_held_chips_reads_the_open_device_files(tmp_path, monkeypatch):
    """What a process holds, from /proc/<pid>/fd: /dev/accel<N> or
    /dev/vfio/<N>, never the vfio container file."""
    links = {"3": "/dev/vfio/2", "4": "/dev/vfio/vfio", "5": "/dev/accel1",
             "6": "/tmp/x", "7": "/dev/vfio/2"}
    monkeypatch.setattr(chips_mod.os, "listdir", lambda p: list(links))
    monkeypatch.setattr(chips_mod.os, "readlink",
                        lambda p: links[p.rsplit("/", 1)[1]])
    assert chips_mod.held_chips() == [1, 2]
    assert chips_mod.held_chips(pid="0") == [1, 2]
    monkeypatch.undo()
    assert chips_mod.held_chips() == []  # no chip in a test process
    assert chips_mod.held_chips(pid="no-such-pid") == []


def test_host_chips_counts_the_hosts_chip_device_files(monkeypatch):
    """How many chips a launcher passes as `chips`: asked of /dev, never
    of jax."""
    dirs = {"/dev": ["null", "accel0", "accel1", "accelerometer", "vfio"],
            "/dev/vfio": ["vfio", "0", "1", "2", "3"]}
    monkeypatch.setattr(chips_mod.os, "listdir", lambda d: dirs[d])
    assert chips_mod.host_chips() == 6
    dirs["/dev"] = ["null", "vfio"]
    assert chips_mod.host_chips() == 4   # the chip machine: /dev/vfio/0-3

    def no_vfio(d):
        if d == "/dev/vfio":
            raise FileNotFoundError(d)
        return ["null", "tty"]

    monkeypatch.setattr(chips_mod.os, "listdir", no_vfio)
    assert chips_mod.host_chips() == 0   # off a TPU


def test_chip_is_the_device_file_held_and_absent_where_none_is(monkeypatch):
    """`chip` is never the jax device id under another name: where no
    chip device file is held the key is left out, and the gauge with
    it."""
    from gatekeeper_tpu.metrics.catalog import record_replica_chip
    from gatekeeper_tpu.metrics.exporter import render_prometheus
    from gatekeeper_tpu.parallel import mesh

    info = mesh.chip_info()
    assert set(info) == {"device_kind"} and info["device_kind"]
    monkeypatch.setattr(mesh, "held_chips", lambda: [2, 3])
    assert mesh.chip_info() == {"chip": 2, **info}
    record_replica_chip(2)
    assert [line for line in render_prometheus().splitlines()
            if "replica_chip_info{" in line and 'chip="2"' in line
            and line.endswith(" 1")]


# ---- _spawn_proc: chips=1 is today's launch, byte for byte ------------------


class _Popen:
    calls = []

    def __init__(self, cmd, **kw):
        _Popen.calls.append((cmd, kw))


@pytest.fixture()
def popen(monkeypatch):
    _Popen.calls = []
    monkeypatch.setattr(rep.subprocess, "Popen", _Popen)
    monkeypatch.setenv("GK_TEST_MARK", "kept")
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "from-the-parent")
    return _Popen.calls


def todays_launch(replica_id, snapshot_dir, cache_dir, extra_flags, env):
    """What _spawn_proc built before it knew of chips (PR 34's tree)."""
    cmd = [sys.executable, "-m", "gatekeeper_tpu.fleet.replica",
           "--replica-id", replica_id]
    if snapshot_dir:
        cmd += ["--snapshot-dir", snapshot_dir]
    if cache_dir:
        cmd += ["--xla-cache-dir", cache_dir]
    cmd += list(extra_flags)
    child_env = dict(os.environ)
    if env:
        child_env.update(env)
    return cmd, child_env


@pytest.mark.parametrize("args", [
    ("r0", "", "", (), None),
    ("r1", "/snap", "/cache", ("--driver", "interp"), {"A": "b"}),
    ("r7", "/snap", "", ("--webhook-batch-static",),
     {"JAX_PLATFORMS": "cpu", "GK_TEST_MARK": "overridden"}),
])
@pytest.mark.parametrize("placed", [
    {}, {"index": 3}, {"index": 3, "chips": 1}, {"index": 0, "chips": 0}])
def test_a_launch_with_one_chip_is_todays_byte_for_byte(popen, args, placed):
    rep._spawn_proc(*args, **placed)
    (cmd, kw), = popen
    want_cmd, want_env = todays_launch(*args)
    assert cmd == want_cmd
    assert kw["env"] == want_env
    assert list(kw["env"].items()) == list(want_env.items())  # order too
    assert json.dumps(kw["env"]).encode() == json.dumps(want_env).encode()
    assert kw["cwd"] == rep.REPO_ROOT and kw["start_new_session"] is True


def test_a_launch_on_four_chips_lays_the_placement_over_the_env(popen):
    rep._spawn_proc("r5", "/snap", "", (), {"TPU_PROCESS_BOUNDS": "2,2,1"},
                    index=5, chips=4)
    (cmd, kw), = popen
    want_cmd, base = todays_launch("r5", "/snap", "", (), None)
    assert cmd == want_cmd  # no flag on the replica: the env carries it
    assert kw["env"] == {**base, **placement_env(5, 4)}
    assert kw["env"]["TPU_VISIBLE_CHIPS"] == "1"       # 5 % 4, not the parent's
    assert kw["env"]["TPU_PROCESS_BOUNDS"] == "1,1,1"  # nor the caller's
    assert kw["env"]["GK_TEST_MARK"] == "kept"


def test_spawn_fleet_gives_replica_i_chip_i_mod_chips(monkeypatch):
    seen = []

    def fake(replica_id, snapshot_dir, cache_dir, extra_flags, env,
             timeout_s, index=0, chips=1):
        seen.append((replica_id, index, chips))
        return replica_id

    monkeypatch.setattr(rep, "spawn_replica", fake)
    assert rep.spawn_fleet(3, chips=4) == ["r0", "r1", "r2"]
    assert seen == [("r0", 0, 4), ("r1", 1, 4), ("r2", 2, 4)]
    seen.clear()
    rep.spawn_fleet(2)
    assert seen == [("r0", 0, 1), ("r1", 1, 1)]  # the default places nothing


# ---- the supervisor keeps the index in the slot -----------------------------


class PlacedSpawner(FakeSpawner):
    def __init__(self):
        super().__init__()
        self.placed = []

    def __call__(self, replica_id, *a, index=0, chips=1, **kw):
        self.placed.append((replica_id, index, chips))
        return super().__call__(replica_id, *a, index=index, chips=chips,
                                **kw)


@pytest.fixture()
def placed(monkeypatch):
    fake = PlacedSpawner()
    monkeypatch.setattr(sup_mod, "spawn_replica", fake)
    return fake


def test_restart_revive_and_rolling_restart_return_to_the_slots_chip(placed):
    sup = make_supervisor(chips=4, flap_threshold=2, flap_window_s=60.0)
    try:
        handles = sup.start(3)
        assert placed.placed == [("r0", 0, 4), ("r1", 1, 4), ("r2", 2, 4)]
        assert [h.index for h in handles] == [0, 1, 2]
        # a crash: _restart respawns r2 with r2's index, not a fresh one
        handles[2].kill()
        assert wait_until(lambda: placed.placed.count(("r2", 2, 4)) == 2)
        assert wait_until(
            lambda: sup.status()["r2"]["state"] == "running")
        # a second crash inside the flap window quarantines; revive re-arms
        next(h for h in sup.handles() if h.replica_id == "r2").kill()
        assert wait_until(
            lambda: sup.status()["r2"]["state"] == "quarantined")
        sup.revive("r2")
        assert wait_until(lambda: placed.placed.count(("r2", 2, 4)) == 3)
        assert wait_until(
            lambda: sup.status()["r2"]["state"] == "running")
        # a rolling restart puts every replica back where it was
        n = len(placed.placed)
        rolled = sup.rolling_restart(drain_deadline_ms=200.0)
        assert all(r["ok"] for r in rolled.values())
        assert sorted(placed.placed[n:]) == [
            ("r0", 0, 4), ("r1", 1, 4), ("r2", 2, 4)]
        assert {h.replica_id: h.index for h in sup.handles()} == {
            "r0": 0, "r1": 1, "r2": 2}
    finally:
        sup.stop()


def test_an_adopted_replica_keeps_its_handles_index(placed):
    sup = make_supervisor(chips=4)
    try:
        h = placed("r9", index=9, chips=4)
        sup.adopt(h)
        sup.start_monitor()
        h.kill()
        assert wait_until(lambda: placed.placed.count(("r9", 9, 4)) == 2)
    finally:
        sup.stop()


def test_the_default_supervisor_places_nothing(placed):
    sup = make_supervisor()
    try:
        sup.start(2)
        assert placed.placed == [("r0", 0, 1), ("r1", 1, 1)]
    finally:
        sup.stop()


# ---- how a choice fell ------------------------------------------------------


def backends(n):
    return [{"host": "127.0.0.1", "port": 1000 + i, "replica_id": f"r{i}"}
            for i in range(n)]


def test_one_backend_is_always_the_least():
    r = Roster(backends(1))
    for _ in range(5):
        r.choose()
    assert r.take_choices() == {("r0", "least"): 5}
    assert r.take_choices() == {}  # taken is forgotten


def test_equal_backends_tie_and_rotation_shares_them():
    r = Roster(backends(4))
    picked = [r.choose() for _ in range(4)]  # 0 in flight everywhere
    assert sorted(b.replica_id for b in picked) == ["r0", "r1", "r2", "r3"]
    taken = r.take_choices()
    # the last of four: three hold one each, one holds none -> least
    assert sum(taken.values()) == 4
    assert sum(n for (_r, how), n in taken.items() if how == "tie") == 3
    assert sum(n for (_r, how), n in taken.items() if how == "least") == 1


def test_a_strictly_least_backend_is_chosen_as_least():
    r = Roster(backends(3))
    a, b, c = r.backends
    a.inflight, b.inflight, c.inflight = 5, 2, 7
    assert r.choose() is b
    assert r.take_choices() == {("r1", "least"): 1}
    a.inflight, b.inflight, c.inflight = 2, 2, 7
    got = r.choose()
    assert got in (a, b)
    assert r.take_choices() == {(got.replica_id, "tie"): 1}


def test_the_general_path_counts_too_and_round_robin_counts_nothing():
    r = Roster(backends(3))
    a, b, c = r.backends
    b.inflight = 4
    c.inflight = 4
    assert r.choose(exclude={a}) in (b, c)     # a retry: the general path
    assert list(r.take_choices().values()) == [1]
    r.eject(a, "test")
    b.inflight, c.inflight = 1, 3
    assert r.choose(exclude={a}) is b
    assert r.take_choices() == {("r1", "least"): 1}
    rr = Roster(backends(3), policy="round_robin")
    for _ in range(6):
        rr.choose()
    assert rr.take_choices() == {}


def test_every_choice_is_counted_once_under_the_bound():
    r = Roster(backends(2), max_inflight=2)
    for _ in range(4):
        r.choose()
    with pytest.raises(Exception):
        r.choose()  # every backend at its bound: a shed is no choice
    assert sum(r.take_choices().values()) == 4


# ---- two replicas behind one door, on the CPU -------------------------------

# 50 resources: make_pods names 50 namespaces, and a replica seeds its
# store with the namespaces of the pack it restored
N_TEMPLATES, N_RESOURCES, N_REVIEWS = 4, 50, 240


def choice_counts():
    from gatekeeper_tpu.metrics.exporter import render_prometheus

    out = {}
    for line in render_prometheus().splitlines():
        if line.startswith("gatekeeper_frontdoor_choice_total{") \
                or line.startswith("frontdoor_choice_total{"):
            labels, _, v = line.rpartition(" ")
            rid = labels.partition('replica_id="')[2].partition('"')[0]
            how = labels.partition('how="')[2].partition('"')[0]
            out[(rid, how)] = out.get((rid, how), 0) + float(v)
    return out


@spawn_available
def test_two_replicas_behind_one_door_answer_with_the_references_bytes(
        tmp_path):
    """A fleet of two on the CPU: every seeded review through the door
    is answered 200 with the interpreter oracle's verdict and messages,
    both replicas serve, each says what device it runs on, and
    frontdoor_choice_total counts every choice (least + tie)."""
    import re

    sys.path.insert(0, os.path.join(rep.REPO_ROOT, "tools"))
    import check_fleet_parity as chk

    from gatekeeper_tpu.fleet import EventFrontDoor, spawn_fleet
    from gatekeeper_tpu.snapshot import Snapshotter
    from gatekeeper_tpu.util.synthetic import (build_driver, build_oracle,
                                               make_pods)

    snap_dir = str(tmp_path / "snap")
    os.makedirs(snap_dir)
    client = build_driver(N_TEMPLATES, N_RESOURCES)
    client.audit_capped(50)
    assert Snapshotter(client, snap_dir, interval_s=0.0).write_once()
    pods = make_pods(N_REVIEWS, seed=3_000_000_019, violation_rate=0.3)
    reqs = [{"uid": f"placement-{i}",
             "kind": {"group": "", "version": "v1", "kind": "Pod"},
             "name": p["metadata"]["name"],
             "namespace": p["metadata"]["namespace"], "operation": "CREATE",
             "userInfo": {"username": "placement"}, "object": p}
            for i, p in enumerate(pods)]
    oracle = build_oracle(N_TEMPLATES, N_RESOURCES)
    want = []
    for req in reqs:
        res = oracle.review({k: req[k] for k in (
            "kind", "name", "namespace", "operation", "object")}).results()
        want.append((not res, sorted(r.msg for r in res)))

    before = choice_counts()
    fleet, door = [], None
    try:
        fleet = spawn_fleet(2, snapshot_dir=snap_dir,
                            env={"JAX_PLATFORMS": "cpu"}, sequential=False)
        for h in fleet:
            assert h.ready["restore_outcome"] == "restored"
            assert "chip" not in h.ready   # off a TPU no chip file is held
            assert h.ready["device_kind"] == h.ready["device"]["device_kind"]
        assert [h.index for h in fleet] == [0, 1]
        door = EventFrontDoor([h.wire_backend() for h in fleet]).start()
        got = [None] * len(reqs)

        def drive(k, lanes):
            for i in range(k, len(reqs), lanes):
                got[i] = chk._post(
                    door.port, json.dumps({"request": reqs[i]}).encode())

        lanes = 6
        threads = [threading.Thread(target=drive, args=(k, lanes))
                   for k in range(lanes)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(120)
        served = {}
        for i, (status, headers, data) in enumerate(got):
            assert status == 200, (i, status, data[:200])
            out = json.loads(data)["response"]
            assert out["uid"] == reqs[i]["uid"]
            msgs = sorted(
                re.sub(r"^\[denied by [^\]]+\] ", "", m)
                for m in (out.get("status") or {}).get(
                    "message", "").split("\n") if m
            ) if not out["allowed"] else []
            assert (out["allowed"], msgs) == want[i], i
            rid = headers["X-GK-Replica"]
            served[rid] = served.get(rid, 0) + 1
        assert sorted(served) == ["r0", "r1"]
        assert min(served.values()) >= N_REVIEWS * 0.15
        stats = {b["replica_id"]: b for b in door.stats()["backends"]}
        assert not any(b["ejected"] or b["readmissions"]
                       for b in stats.values())
    finally:
        if door is not None:
            door.stop()   # drains the last tick's counts
        for h in fleet:
            h.stop()
    grown = {k: v - before.get(k, 0) for k, v in choice_counts().items()}
    grown = {k: v for k, v in grown.items() if v}
    assert {how for _r, how in grown} <= {"least", "tie"}
    assert sum(grown.values()) == N_REVIEWS   # one choice a review, no retry
    for rid in ("r0", "r1"):  # every review a backend served, it was chosen for
        assert sum(v for (r, _h), v in grown.items() if r == rid) \
            == served[rid]
