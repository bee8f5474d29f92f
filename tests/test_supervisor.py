"""Replica supervision (ISSUE 8, fleet/supervisor.py): crash and wedge
detection, warm restart with backoff, flap quarantine, rolling restart,
and the process-group cleanup that prevents zombie children.

All against a FAKE replica child (a stdlib HTTP server + the replica
command-pipe protocol, no jax import), so supervision logic is proven in
milliseconds; the real-replica end-to-end loop is
tools/check_self_heal.py (tier-1 via tests/test_self_heal_tool.py)."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

import gatekeeper_tpu.fleet.replica as rep
import gatekeeper_tpu.fleet.supervisor as sup_mod
from gatekeeper_tpu.fleet.supervisor import (
    QUARANTINED, RUNNING, ReplicaSupervisor,
)

from .test_snapshot_concurrent import spawn_available

pytestmark = spawn_available


# a stand-in replica speaking the replica protocol: ready line, /healthz,
# ping/drain (+reply_to), a "wedge" command that stops the pipe answering,
# and a flaky mode that exits shortly after ready
FAKE_CHILD = r"""
import json, os, sys, threading, time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

mode = sys.argv[1] if len(sys.argv) > 1 else "ok"

class H(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    def log_message(self, *a): pass
    def _r(self, code, body):
        self.send_response(code)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)
    def do_GET(self): self._r(200, b"ok")
    def do_POST(self):
        n = int(self.headers.get("Content-Length", 0))
        self.rfile.read(n)
        self._r(200, json.dumps({"pid": os.getpid()}).encode())

srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
threading.Thread(target=srv.serve_forever, daemon=True).start()
print(json.dumps({
    "event": "ready", "replica_id": sys.argv[2], "port":
    srv.server_address[1], "metrics_port": srv.server_address[1],
    "ready_s": 0.01, "restore_outcome": "restored",
    "templates": 0,
}), flush=True)
if mode == "flaky":
    threading.Thread(
        target=lambda: (time.sleep(0.15), os._exit(9)), daemon=True
    ).start()
wedged = False
for line in sys.stdin:
    line = line.strip()
    if not line:
        continue
    try:
        cmd = json.loads(line)
    except ValueError:
        continue
    if wedged:
        continue
    def reply(p, cmd=cmd):
        if "id" in cmd:
            p = {**p, "reply_to": cmd["id"]}
        print(json.dumps(p), flush=True)
    op = cmd.get("cmd")
    if op == "ping":
        reply({"event": "pong"})
    elif op == "wedge":
        wedged = True
    elif op == "drain":
        reply({"event": "drained", "pending_start": 0, "drained": True,
               "overran": False, "drain_ms": 0.1})
"""


class FakeSpawner:
    """spawn_replica stand-in using the REAL pipe machinery (demux,
    ready-wait) against the fake child."""

    def __init__(self):
        self.mode = "ok"
        self.calls = 0

    def __call__(self, replica_id, snapshot_dir="", cache_dir="",
                 extra_flags=(), env=None, timeout_s=30.0,
                 index=0, chips=1):
        self.calls += 1
        t0 = time.monotonic()
        proc = subprocess.Popen(
            [sys.executable, "-c", FAKE_CHILD, self.mode, replica_id],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, start_new_session=True,
        )
        pipes = rep._attach_pipes(proc, replica_id)
        ready = rep._wait_ready(proc, replica_id, pipes, t0, timeout_s)
        return rep.ReplicaHandle(
            proc, replica_id, ready, round(time.monotonic() - t0, 3), pipes,
            index,
        )


def wait_until(cond, timeout_s=20.0, step_s=0.05):
    end = time.monotonic() + timeout_s
    while time.monotonic() < end:
        if cond():
            return True
        time.sleep(step_s)
    return cond()


@pytest.fixture()
def spawner(monkeypatch):
    fake = FakeSpawner()
    monkeypatch.setattr(sup_mod, "spawn_replica", fake)
    return fake


def make_supervisor(changes=None, **kw):
    kw.setdefault("heartbeat_s", 0.05)
    kw.setdefault("probe_timeout_s", 0.5)
    kw.setdefault("miss_threshold", 2)
    kw.setdefault("backoff_base_s", 0.05)
    kw.setdefault("backoff_cap_s", 0.4)
    kw.setdefault("spawn_timeout_s", 30.0)
    if changes is not None:
        kw["on_backend_change"] = lambda rid, b: changes.append((rid, b))
    return ReplicaSupervisor(**kw)


class TestCrashRecovery:
    def test_killed_replica_is_restarted_and_door_repointed(self, spawner):
        changes = []
        sup = make_supervisor(changes)
        try:
            (h,) = sup.start(1)
            pid0, port0 = h.proc.pid, h.port
            os.kill(pid0, signal.SIGKILL)
            assert wait_until(lambda: (
                sup.status()["r0"]["state"] == "running"
                and sup.status()["r0"]["pid"] not in (None, pid0)
            )), f"no restart: {sup.status()}"
            st = sup.status()["r0"]
            assert st["restarts"] == 1
            assert st["last_exit_rc"] == -signal.SIGKILL
            # door sequencing: spawn(backend), eject(None), readmit(new)
            kinds = [(rid, b is None) for rid, b in changes]
            assert kinds[0] == ("r0", False)
            assert ("r0", True) in kinds
            assert kinds[-1] == ("r0", False)
            new_backend = changes[-1][1]
            assert new_backend["port"] == sup.status()["r0"]["port"]
            assert new_backend["port"] != port0 or True  # ephemeral
        finally:
            sup.stop()

    def test_wedged_pipe_is_detected_and_restarted(self, spawner):
        """HTTP keeps answering; only the command pipe wedges — the
        command-pipe liveness leg must catch it."""
        sup = make_supervisor()
        try:
            (h,) = sup.start(1)
            pid0 = h.proc.pid
            # wedge the fake's command loop (no reply expected)
            h.proc.stdin.write(json.dumps({"cmd": "wedge"}) + "\n")
            h.proc.stdin.flush()
            assert wait_until(lambda: (
                sup.status()["r0"]["restarts"] >= 1
                and sup.status()["r0"]["state"] == "running"
            )), f"wedge never detected: {sup.status()}"
            assert sup.status()["r0"]["pid"] != pid0
        finally:
            sup.stop()


class TestObservabilityTargets:
    def test_target_rosters_follow_a_restart(self, spawner):
        """trace_targets()/metrics_targets() (the fleet observability
        plane's live rosters, ISSUE 11) must report the CURRENT
        incarnation's ports — a restarted replica's fresh ephemeral
        port, not the dead one's."""
        sup = make_supervisor()
        try:
            (h,) = sup.start(1)
            t0 = sup.trace_targets()
            m0 = sup.metrics_targets()
            assert t0 == [{"replica_id": "r0", "host": h.host,
                           "port": h.port}]
            assert m0[0]["port"] == h.metrics_port > 0
            os.kill(h.proc.pid, signal.SIGKILL)
            assert wait_until(lambda: (
                sup.status()["r0"]["state"] == "running"
                and sup.status()["r0"]["pid"] != h.proc.pid
            ))
            t1 = sup.trace_targets()
            assert len(t1) == 1
            assert t1[0]["port"] == sup.status()["r0"]["port"]
        finally:
            sup.stop()


class TestFlapQuarantine:
    def test_crash_loop_is_quarantined_then_revivable(self, spawner):
        sup = make_supervisor(flap_window_s=30.0, flap_threshold=3)
        try:
            (h,) = sup.start(1)
            spawner.mode = "flaky"  # every respawn dies ~150ms in
            os.kill(h.proc.pid, signal.SIGKILL)
            assert wait_until(
                lambda: sup.status()["r0"]["state"] == "quarantined",
                timeout_s=30.0,
            ), f"never quarantined: {sup.status()}"
            calls_at_quarantine = spawner.calls
            time.sleep(0.6)  # several backoff periods
            assert spawner.calls == calls_at_quarantine, \
                "quarantined replica kept being respawned"
            assert sup.status()["r0"]["quarantined_reason"]
            # operator re-arms it once the cause is fixed
            spawner.mode = "ok"
            sup.revive("r0")
            assert wait_until(
                lambda: sup.status()["r0"]["state"] == "running",
                timeout_s=30.0,
            ), f"revive did not restart: {sup.status()}"
        finally:
            sup.stop()


class TestRollingRestart:
    def test_rolling_restart_drains_and_replaces_every_replica(
        self, spawner
    ):
        changes = []
        sup = make_supervisor(changes)
        try:
            handles = sup.start(2)
            pids = {h.replica_id: h.proc.pid for h in handles}
            out = sup.rolling_restart(drain_deadline_ms=500.0)
            assert sorted(out) == ["r0", "r1"]
            for rid, res in out.items():
                assert res["ok"], res
                assert res["drain"].get("event") == "drained"
                assert res["drain"].get("drained") is True
                assert sup.status()[rid]["pid"] != pids[rid]
            # every replica was ejected before its drain and readmitted
            # after its respawn, in order
            for rid in ("r0", "r1"):
                seq = [b is None for r, b in changes if r == rid]
                assert seq[0] is False          # initial spawn
                assert True in seq              # ejected for the roll
                assert seq[-1] is False         # readmitted at the end
        finally:
            sup.stop()


class TestStateCodes:
    def test_state_gauge_codes_cover_the_ladder(self):
        # the metric contract docs/metrics.md documents
        assert (RUNNING, QUARANTINED) == (0, 2)
        assert sup_mod._STATE_NAMES[3] == "draining"


# ---- zombie hygiene (the killed-parent satellite) ---------------------------

PARENT_SCRIPT = r"""
import os, signal, subprocess, sys, time
sys.path.insert(0, {repo!r})
from gatekeeper_tpu.fleet import supervisor as sup

child = subprocess.Popen(
    [sys.executable, "-c", "import time; time.sleep(120)"],
    start_new_session=True,
)
sup.install_cleanup()
sup._register_group(child.pid)
print(child.pid, flush=True)
time.sleep(120)
"""


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True


class TestProcessGroupCleanup:
    def test_sigterm_on_parent_kills_supervised_groups(self, tmp_path):
        """The satellite: ReplicaHandle children must not outlive a dead
        parent.  SIGTERM the parent; its cleanup handler SIGKILLs every
        registered replica process group."""
        parent = subprocess.Popen(
            [sys.executable, "-c",
             PARENT_SCRIPT.format(repo=os.path.dirname(
                 os.path.dirname(os.path.abspath(__file__))))],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        try:
            line = parent.stdout.readline().strip()
            child_pid = int(line)
            assert _pid_alive(child_pid)
            parent.send_signal(signal.SIGTERM)
            parent.wait(timeout=15)
            assert wait_until(lambda: not _pid_alive(child_pid),
                              timeout_s=10.0), \
                "replica child survived the parent's SIGTERM"
        finally:
            if parent.poll() is None:
                parent.kill()
                parent.wait(timeout=5)

    def test_orderly_exit_reaps_groups_via_atexit(self):
        """Normal interpreter exit runs the same sweeper via atexit."""
        code = PARENT_SCRIPT.format(repo=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        # exit right after announcing the child: atexit must reap it
        code = code.replace("print(child.pid, flush=True)\ntime.sleep(120)",
                            "print(child.pid, flush=True)")
        parent = subprocess.Popen(
            [sys.executable, "-c", code],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        try:
            child_pid = int(parent.stdout.readline().strip())
            parent.wait(timeout=15)
            assert wait_until(lambda: not _pid_alive(child_pid),
                              timeout_s=10.0), \
                "replica child survived the parent's orderly exit"
        finally:
            if parent.poll() is None:
                parent.kill()
                parent.wait(timeout=5)
