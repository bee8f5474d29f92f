"""Child processes and the HTTP surfaces they serve (after
chip_smoke.py's Procs / Http / poll): every process the benchmark
starts is stopped and waited for on every exit path."""

from __future__ import annotations

import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class BenchFailure(Exception):
    """The run cannot produce a result; the message says why."""


class Procs:
    def __init__(self):
        self.live = []

    def popen(self, cmd, log_path, env, cpus=None, pipes=False):
        """A child in a session of its own, pinned to `cpus` if given,
        its output in log_path; with `pipes` its stdin and stdout are
        text pipes (only stderr goes to the log)."""
        logf = open(log_path, "ab")
        pre = (lambda: os.sched_setaffinity(0, cpus)) if cpus else None
        io = (dict(stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                   stderr=logf, text=True) if pipes
              else dict(stdin=subprocess.DEVNULL, stdout=logf,
                        stderr=subprocess.STDOUT))
        proc = subprocess.Popen(cmd, cwd=ROOT, env=env, preexec_fn=pre,
                                start_new_session=True, **io)
        logf.close()
        self.live.append(proc)
        return proc

    @staticmethod
    def stop(proc, grace_s: float = 10.0):
        if proc.stdin is not None:
            try:
                proc.stdin.close()  # a replica's lifetime is its stdin
            except OSError:
                pass
        for sig, wait_s in ((signal.SIGINT, grace_s), (signal.SIGKILL, 10)):
            if proc.poll() is not None:
                break
            try:
                os.killpg(proc.pid, sig)
                proc.wait(timeout=wait_s)
            except (ProcessLookupError, PermissionError,
                    subprocess.TimeoutExpired):
                pass

    def stop_all(self):
        for proc in reversed(self.live):
            self.stop(proc, grace_s=3.0)
        self.live = []


def child_env(extra: dict = None) -> dict:
    """The same bytes every run of a seed: a pinned hash seed (intern
    order decides vocabulary ids), the native packer or an error."""
    env = dict(os.environ)
    env.update({"PYTHONHASHSEED": "0", "PYTHONUNBUFFERED": "1",
                "GK_NATIVE": "require",
                "PYTHONPATH": ROOT + os.pathsep + env.get("PYTHONPATH", "")})
    env.update(extra or {})
    return env


def cpu_sets(parts: dict) -> dict:
    """Disjoint CPU sets for the named processes, sized by weight from
    the CPUs this process may use: {"replica": 6, "door": 2, ...}.  With
    fewer CPUs than names nothing is pinned."""
    cpus = sorted(os.sched_getaffinity(0))
    if len(cpus) < 2 * len(parts):
        return {k: None for k in parts}
    total = sum(parts.values())
    out, at = {}, 0
    names = list(parts)
    for i, k in enumerate(names):
        n = max(1, int(len(cpus) * parts[k] / total))
        if i == len(names) - 1:
            n = len(cpus) - at
        out[k] = set(cpus[at:at + n])
        at += n
    return out


def log_tail(path: str, n: int = 25, width: int = 300) -> str:
    try:
        with open(path, "r", errors="replace") as f:
            return "".join(ln[:width] + ("" if len(ln) <= width else "...\n")
                           for ln in f.readlines()[-n:])
    except OSError as e:
        return f"<no log: {e}>"


def wait_child(proc, what: str, log_path: str, timeout_s: float):
    try:
        rc = proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        Procs.stop(proc, grace_s=1.0)
        raise BenchFailure(f"{what} did not finish in {timeout_s:.0f}s; "
                           "log tail:\n" + log_tail(log_path))
    if rc != 0:
        raise BenchFailure(f"{what} exited rc={rc}; log tail:\n"
                           + log_tail(log_path), rc)


def http_get(port: int, path: str, timeout: float = 30.0) -> bytes:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        data = resp.read()
        if resp.status != 200:
            raise ConnectionError(f"GET {path} -> {resp.status}")
        return data
    except (http.client.HTTPException, socket.timeout) as e:
        raise ConnectionError(f"GET :{port}{path}: {e!r}")
    finally:
        conn.close()


def get_json(port: int, path: str):
    return json.loads(http_get(port, path))


_SAMPLE = re.compile(r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})?\s+(\S+)$")


def scrape(port: int) -> dict:
    """A /metrics page as {"name{labels}": value}."""
    out = {}
    for line in http_get(port, "/metrics").decode("utf-8", "replace") \
            .splitlines():
        m = _SAMPLE.match(line)
        if m:
            try:
                out[m.group(1) + (m.group(2) or "")] = float(m.group(3))
            except ValueError:
                pass
    return out


def poll(what: str, timeout_s: float, fn, proc=None, log_path=None,
         every_s: float = 0.2):
    """fn() until it returns something truthy; a dead child or the
    timeout is a failure that names what was being waited for."""
    deadline = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < deadline:
        if proc is not None and proc.poll() is not None:
            raise BenchFailure(
                f"child exited rc={proc.returncode} while waiting for "
                f"{what}; log tail:\n" + log_tail(log_path), proc.returncode)
        try:
            got = fn()
            if got:
                return got
        except (ConnectionError, OSError, ValueError, KeyError) as e:
            last = e
        time.sleep(every_s)
    raise BenchFailure(f"timed out after {timeout_s:.0f}s waiting for {what}"
                       + (f" (last error: {last!r})" if last else "")
                       + ("; log tail:\n" + log_tail(log_path)
                          if log_path else ""))


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def write_json(path: str, payload):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)


def python(*args) -> list:
    return [sys.executable, *args]
