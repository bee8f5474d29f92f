"""Test configuration: force JAX onto a virtual 8-device CPU mesh so
multi-chip sharding paths compile and execute without TPU hardware
(the driver separately dry-runs the same code via __graft_entry__)."""

import os

# Tests run on the local CPU backend whatever the machine holds (the chip
# is exercised by chip_smoke.py, one process at a time): both variables
# must be set BEFORE any jax import.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

# Apps built by tests get no persistent compile cache unless the test
# passes --xla-cache-dir or sets $JAX_COMPILATION_CACHE_DIR: one cache
# shared by the whole session would make every compile-provenance and
# AOT assertion depend on test order (production default:
# <checkout>/.xla-cache, ops/xlacache.py).
from gatekeeper_tpu.ops import xlacache  # noqa: E402

xlacache.DEFAULT_CACHE_DIR = ""

REFERENCE = pathlib.Path("/root/reference")


def reference_available() -> bool:
    return REFERENCE.exists()


# Deterministic delta-path tests: give the background base-mask resolution
# time to land (CPU-backend compiles finish well within this) instead of
# falling back to a full sweep.  Production keeps the wait near zero
# because it happens under the driver lock (ops/driver.py).
from gatekeeper_tpu.ops.driver import TpuDriver  # noqa: E402

TpuDriver.DELTA_MASK_WAIT_S = 300.0

# The sweeps' heap discipline (util/heap.py) engages when a full
# collection lands inside a sweep.  A pytest worker lives for hundreds
# of tests and its collections land where they will: pinned off, so no
# test's sweep freezes the worker's heap and leaves its collector
# disabled (tests/test_sweep_heap.py sets the gate itself).
from gatekeeper_tpu.util import heap  # noqa: E402

heap.ENGAGE_MIN_PAUSE_S = float("inf")

# ---- chaos hygiene: no test may leak live fault-plane state or threads -----

import threading  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _gk_logger_isolation():
    """gklog.setup() (run by App startup) attaches a handler to the
    'gatekeeper' logger and disables propagation — process-wide.  Restore
    the logger after every test so an App-constructing test doesn't break
    caplog-based assertions for the rest of the session."""
    import logging as _logging

    root = _logging.getLogger("gatekeeper")
    level, handlers, propagate = root.level, root.handlers[:], root.propagate
    yield
    root.setLevel(level)
    root.handlers[:] = handlers
    root.propagate = propagate


def _listening_socket_inodes():
    """Inodes of this process's LISTEN-state TCP sockets (v4+v6), or
    None when /proc is unavailable (non-Linux).  Inode identity — not fd
    numbers — so dup()ed fds of one socket count once and fd-number
    reuse across tests cannot alias."""
    import re

    inodes = set()
    for path in ("/proc/net/tcp", "/proc/net/tcp6"):
        try:
            with open(path) as f:
                next(f, None)
                for line in f:
                    parts = line.split()
                    if len(parts) > 9 and parts[3] == "0A":  # LISTEN
                        inodes.add(parts[9])
        except OSError:
            return None
    held = set()
    try:
        for fd in os.listdir("/proc/self/fd"):
            try:
                target = os.readlink(f"/proc/self/fd/{fd}")
            except OSError:
                continue  # fd closed between listdir and readlink
            m = re.match(r"socket:\[(\d+)\]", target)
            if m and m.group(1) in inodes:
                held.add(m.group(1))
    except OSError:
        return None
    return held


@pytest.fixture(autouse=True)
def _no_listener_leaks():
    """Fail any test that leaves a new LISTENING socket open — the
    file-descriptor complement of the thread-leak fixture below, and the
    runtime twin of gklint's static `listener-close`/`start-guard` rules
    (tools/gklint.py).  A leaked listener holds its port for the rest of
    the session: the next test binding the same --port gets EADDRINUSE
    minutes away from the actual culprit.  Servers must stop via
    close_listener()/server_close() (WebhookServer.stop, exporter.stop,
    EventFrontDoor.stop...)."""
    import time as _t

    before = _listening_socket_inodes()
    yield
    if before is None:
        return  # no /proc: nothing to check on this platform
    deadline = _t.monotonic() + 2.0
    while _t.monotonic() < deadline:
        after = _listening_socket_inodes()
        leaked = (after or set()) - before
        if not leaked:
            return
        _t.sleep(0.05)  # teardown threads may still be closing
    pytest.fail(
        f"test leaked {len(leaked)} listening socket(s) — close servers "
        "via close_listener()/server_close() in stop() "
        "(gklint: listener-close)"
    )


@pytest.fixture(autouse=True)
def _no_fault_or_thread_leaks():
    """Fail any test that leaves the process-global fault plane enabled or
    leaks a non-daemon thread.  A leaked plane would inject faults into
    every later test (order-dependent carnage); a leaked non-daemon thread
    would hang the pytest process at exit.  The plane is force-uninstalled
    before failing so the rest of the session stays clean."""
    from gatekeeper_tpu import faults

    baseline = {t for t in threading.enumerate() if not t.daemon}
    yield
    leaked_plane = faults.ENABLED
    if leaked_plane:
        faults.uninstall()  # contain the damage before reporting it
    stragglers = [
        t for t in threading.enumerate()
        if not t.daemon and t.is_alive() and t not in baseline
    ]
    for t in stragglers:  # short grace: threads mid-teardown may finish
        t.join(timeout=1.0)
    stragglers = [t for t in stragglers if t.is_alive()]
    if leaked_plane:
        pytest.fail(
            "test leaked an enabled fault plane — call faults.uninstall() "
            "(or use the chaos suite's fault_plane fixture)"
        )
    if stragglers:
        pytest.fail(
            "test leaked non-daemon threads: "
            + ", ".join(t.name for t in stragglers)
        )
